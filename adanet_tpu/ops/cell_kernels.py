"""Pallas TPU kernel: one fused NASNet-A cell (ROADMAP item 1, MFU
campaign axis 2).

`ops/sepconv_kernels.py` fuses one relu → depthwise → pointwise triple;
a NASNet-A cell chains ten of those branches plus pools, branch adds,
the final concat, and (in reduction cells) factorized reductions of the
skip states — today each of those is a separate XLA op with an HBM
round-trip of a [B, H, W, F] intermediate between every pair. This
kernel keeps the WHOLE cell VMEM-resident per batch tile:

    HBM reads:  prev, cur (once each), the cell's weights
    in VMEM:    begin 1x1 → 5 blocks of (branch op + branch op + add)
                → concat of unused states → factorized reductions
    HBM write:  the cell output (once)

The cell is computed in its *folded-affine* form: every batch-norm is
represented as a per-channel (scale, bias) pair — the inference-mode
form after statistics are folded in, and the form under which the cell
is a pure function of its inputs (training-mode BN needs cross-tile
batch statistics, which a per-tile kernel cannot produce; the training
path keeps `models/nasnet.py`'s per-op composition with the fused
sep-conv kernel. This primitive serves the serving/eval path and the
autotuner's search space).

Oracle contract: `cell_reference` is the UNFUSED composition — the same
branch math as separate jnp ops with HBM between them — and the kernel
body calls the *identical* helper functions on its VMEM tile, so the
interpret-mode kernel is bit-identical to the jit-compiled reference on
CPU (asserted by tests/test_cell_kernel.py; eager op-by-op dispatch can
differ at 1 ulp from the jitted program, so the oracle compares the
form production actually runs — under jit). A second anchor test checks
the shifted-MAC sep-conv math against `lax.conv_general_dilated` to
tolerance, tying the oracle to the framework's convolution semantics.

Differentiability: custom VJP whose backward re-derives gradients from
the reference (one extra forward — the NasNetConfig.remat trade), like
`fused_sep_conv`. Which shapes take the kernel is a static rule
(`kernel_takes`); inside it a TPU compiler refusal raises. Block sizes
consult the store-persisted autotuner (`ops/tuning.py`) before the
static VMEM heuristic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from adanet_tpu.ops import tuning
from adanet_tpu.ops.sepconv_kernels import _same_pads

# Per-tile VMEM budget (bytes): the whole state list of one cell must
# stay resident, so the budget is tighter per example than the single
# sep-conv kernel's.
_VMEM_BUDGET = 6 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """Static structure of one cell: the NASNet-A wiring tables.

    `operations[2b]`/`operations[2b+1]` are block b's left/right branch
    ops applied to `states[hiddenstate_indices[2b]]` /
    `states[hiddenstate_indices[2b+1]]`; `used_hiddenstates[i] == 0`
    marks `states[i]` for the final concat. `stride` > 1 makes this a
    reduction cell: branch ops consuming an ORIGINAL input (state index
    < 2) apply the stride, later states are already reduced
    (models/nasnet.py `_apply_operation`).

    Supported ops: `separable_<k>x<k>_<n>`, `avg_pool_3x3`,
    `max_pool_3x3`, `none`. Hashable (all-tuple fields) so it can ride
    as a `custom_vjp` nondiff argument.
    """

    operations: Tuple[str, ...]
    hiddenstate_indices: Tuple[int, ...]
    used_hiddenstates: Tuple[int, ...]
    stride: int = 1

    def __post_init__(self):
        if len(self.operations) != len(self.hiddenstate_indices):
            raise ValueError("operations / hiddenstate_indices mismatch")
        if len(self.operations) % 2:
            raise ValueError("operations must pair up into blocks")
        if len(self.used_hiddenstates) != 2 + self.num_blocks:
            raise ValueError(
                "used_hiddenstates must cover 2 inputs + %d blocks"
                % self.num_blocks
            )

    @property
    def num_blocks(self) -> int:
        return len(self.operations) // 2


# The NASNet-A wiring (models/nasnet.py tables), importable by name so
# the autotuner and tests agree on the flagship specs.
NORMAL_CELL = CellSpec(
    operations=(
        "separable_5x5_2",
        "separable_3x3_2",
        "separable_5x5_2",
        "separable_3x3_2",
        "avg_pool_3x3",
        "none",
        "avg_pool_3x3",
        "avg_pool_3x3",
        "separable_3x3_2",
        "none",
    ),
    hiddenstate_indices=(0, 1, 1, 1, 0, 1, 1, 1, 0, 0),
    used_hiddenstates=(1, 0, 0, 0, 0, 0, 0),
    stride=1,
)
REDUCTION_CELL = CellSpec(
    operations=(
        "separable_5x5_2",
        "separable_7x7_2",
        "max_pool_3x3",
        "separable_7x7_2",
        "avg_pool_3x3",
        "separable_5x5_2",
        "none",
        "avg_pool_3x3",
        "separable_3x3_2",
        "max_pool_3x3",
    ),
    hiddenstate_indices=(0, 1, 0, 1, 0, 1, 3, 2, 2, 0),
    used_hiddenstates=(1, 1, 1, 0, 0, 0, 0),
    stride=2,
)


def _parse_separable(operation: str) -> Tuple[int, int]:
    parts = operation.split("_")
    return int(parts[1].split("x")[0]), int(parts[2])


def _branch_stride(spec: CellSpec, state_index: int) -> int:
    """The stride a branch actually applies: reductions hit original
    inputs only (models/nasnet.py `_apply_operation` stride demotion)."""
    return spec.stride if state_index < 2 else 1


def init_cell_params(
    rng,
    spec: CellSpec,
    prev_channels: int,
    cur_channels: int,
    filters: int,
    dtype=jnp.float32,
):
    """Initializes the cell's parameter pytree for `spec`.

    Affine (scale, bias) pairs — the folded batch-norms — are always
    float32 (the bf16 policy's deliberate f32 island); conv kernels take
    `dtype`. Structure (all-static given spec + channel widths):

        begin:       1x1 projection of `cur` to `filters` (+ affine)
        prev:        1x1 projection of `prev`, present iff
                     prev_channels != filters
        blocks[b]:   {"left": branch, "right": branch}
        reductions:  {str(i): factorized-reduction params} for every
                     unused full-resolution state a stride-2 cell must
                     match to the reduced output
    """
    init = jax.nn.initializers.lecun_normal()

    def conv1x1(key, in_ch):
        return {
            "w": init(key, (in_ch, filters), dtype),
            "scale": jnp.ones((filters,), jnp.float32),
            "bias": jnp.zeros((filters,), jnp.float32),
        }

    def branch(key, operation, stride):
        if "separable" in operation:
            kernel, num_layers = _parse_separable(operation)
            layers = []
            for i in range(num_layers):
                key, dk, pk = jax.random.split(key, 3)
                layers.append(
                    {
                        "dw": init(dk, (kernel, kernel, 1, filters), dtype),
                        "pw": init(pk, (1, 1, filters, filters), dtype),
                        "scale": jnp.ones((filters,), jnp.float32),
                        "bias": jnp.zeros((filters,), jnp.float32),
                    }
                )
            return {"layers": tuple(layers)}
        if operation == "none" and stride > 1:
            return conv1x1(key, filters)
        return {}

    rng, begin_key = jax.random.split(rng)
    params: Dict[str, Any] = {"begin": conv1x1(begin_key, cur_channels)}
    if prev_channels != filters:
        rng, prev_key = jax.random.split(rng)
        params["prev"] = conv1x1(prev_key, prev_channels)
    blocks = []
    for b in range(spec.num_blocks):
        rng, lk, rk = jax.random.split(rng, 3)
        blocks.append(
            {
                "left": branch(
                    lk,
                    spec.operations[2 * b],
                    _branch_stride(spec, spec.hiddenstate_indices[2 * b]),
                ),
                "right": branch(
                    rk,
                    spec.operations[2 * b + 1],
                    _branch_stride(
                        spec, spec.hiddenstate_indices[2 * b + 1]
                    ),
                ),
            }
        )
    params["blocks"] = tuple(blocks)
    reductions: Dict[str, Any] = {}
    if spec.stride > 1:
        for idx, used in enumerate(spec.used_hiddenstates):
            if not used and idx < 2:
                rng, k1, k2 = jax.random.split(rng, 3)
                reductions[str(idx)] = {
                    "w1": init(k1, (filters, filters // 2), dtype),
                    "w2": init(
                        k2,
                        (filters, filters - filters // 2),
                        dtype,
                    ),
                    "scale": jnp.ones((filters,), jnp.float32),
                    "bias": jnp.zeros((filters,), jnp.float32),
                }
    params["reductions"] = reductions
    return params


# --------------------------------------------------------------------------
# Branch math, shared VERBATIM by the unfused reference and the kernel
# body: the interpret-mode bit-identity contract holds by construction
# (every op is batch-elementwise or row-independent, so batch tiling
# cannot change a single example's arithmetic).
# --------------------------------------------------------------------------


def _affine(x, scale, bias):
    return x * scale.astype(jnp.float32) + bias.astype(jnp.float32)


def _conv1x1(x, p, stride):
    """relu → 1x1 conv (stride via subsampling) → affine, f32."""
    y = jnp.maximum(x, 0.0)
    if stride > 1:
        y = y[:, ::stride, ::stride, :]
    b, h, w, c = y.shape
    out = jax.lax.dot_general(
        y.reshape(b * h * w, c),
        p["w"].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(b, h, w, -1)
    return _affine(out, p["scale"], p["bias"])


def _sepconv_layer(x, layer, stride):
    """relu → k×k depthwise (SAME, shifted MACs) → 1x1 pointwise →
    affine — the `_sepconv_kernel` math on an in-register array."""
    k = layer["dw"].shape[0]
    b, h, w, c = x.shape
    h_out, pt, pb = _same_pads(h, k, stride)
    w_out, plo, pr = _same_pads(w, k, stride)
    y = jnp.maximum(x, 0.0).astype(jnp.float32)
    y = jnp.pad(y, ((0, 0), (pt, pb), (plo, pr), (0, 0)))
    acc = jnp.zeros((b, h_out, w_out, c), jnp.float32)
    for i in range(k):
        for j in range(k):
            patch = jax.lax.slice(
                y,
                (0, i, j, 0),
                (
                    b,
                    i + (h_out - 1) * stride + 1,
                    j + (w_out - 1) * stride + 1,
                    c,
                ),
                (1, stride, stride, 1),
            )
            acc = acc + patch * layer["dw"][i, j, 0, :].astype(jnp.float32)
    out = jax.lax.dot_general(
        acc.reshape(b * h_out * w_out, c),
        layer["pw"][0, 0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(b, h_out, w_out, -1)
    return _affine(out, layer["scale"], layer["bias"])


def _pool(x, kind: str, stride: int):
    """3x3 SAME pool via shifted reads (flax semantics:
    count_include_pad avg; -inf-padded max)."""
    k = 3
    b, h, w, c = x.shape
    h_out, pt, pb = _same_pads(h, k, stride)
    w_out, plo, pr = _same_pads(w, k, stride)
    fill = 0.0 if kind == "avg" else -jnp.inf
    y = jnp.pad(
        x.astype(jnp.float32),
        ((0, 0), (pt, pb), (plo, pr), (0, 0)),
        constant_values=fill,
    )
    acc = None
    for i in range(k):
        for j in range(k):
            patch = jax.lax.slice(
                y,
                (0, i, j, 0),
                (
                    b,
                    i + (h_out - 1) * stride + 1,
                    j + (w_out - 1) * stride + 1,
                    c,
                ),
                (1, stride, stride, 1),
            )
            if acc is None:
                acc = patch
            elif kind == "avg":
                acc = acc + patch
            else:
                acc = jnp.maximum(acc, patch)
    return acc / float(k * k) if kind == "avg" else acc


def _factorized_reduction(x, p):
    """Two-path stride-2 reduction (models/nasnet.py
    `_FactorizedReduction`, final-concat call site: no leading relu)."""
    xf = x.astype(jnp.float32)
    b = xf.shape[0]

    def project(y, w):
        bb, h, w_, c = y.shape
        return jax.lax.dot_general(
            y.reshape(bb * h * w_, c),
            w.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bb, h, w_, -1)

    path1 = project(xf[:, ::2, ::2, :], p["w1"])
    shifted = jnp.pad(xf, ((0, 0), (0, 1), (0, 1), (0, 0)))[:, 1:, 1:, :]
    path2 = project(shifted[:, ::2, ::2, :], p["w2"])
    out = jnp.concatenate([path1, path2], axis=-1)
    return _affine(out, p["scale"], p["bias"])


def _apply_branch(x, operation, params, stride):
    if "separable" in operation:
        y = x
        for layer_index, layer in enumerate(params["layers"]):
            y = _sepconv_layer(y, layer, stride if layer_index == 0 else 1)
        return y
    if "pool" in operation:
        return _pool(x, operation.split("_")[0], stride)
    if operation == "none":
        if stride > 1:
            return _conv1x1(x, params, stride)
        return x.astype(jnp.float32)
    raise ValueError("Unsupported cell operation %r" % operation)


def _cell_body(prev, cur, params, spec: CellSpec):
    """The whole cell on concrete arrays — reference AND kernel body."""
    x = _conv1x1(cur, params["begin"], 1)
    if "prev" in params:
        prev_state = _conv1x1(prev, params["prev"], 1)
    else:
        prev_state = prev.astype(jnp.float32)
    states = [x, prev_state]
    for b, block in enumerate(params["blocks"]):
        left_idx = spec.hiddenstate_indices[2 * b]
        right_idx = spec.hiddenstate_indices[2 * b + 1]
        left = _apply_branch(
            states[left_idx],
            spec.operations[2 * b],
            block["left"],
            _branch_stride(spec, left_idx),
        )
        right = _apply_branch(
            states[right_idx],
            spec.operations[2 * b + 1],
            block["right"],
            _branch_stride(spec, right_idx),
        )
        states.append(left + right)
    final = states[-1]
    to_combine = []
    for idx, used in enumerate(spec.used_hiddenstates):
        if used:
            continue
        state = states[idx]
        if state.shape[1] != final.shape[1]:
            state = _factorized_reduction(
                state, params["reductions"][str(idx)]
            )
        to_combine.append(state)
    return jnp.concatenate(to_combine, axis=-1)


def cell_reference(prev, cur, params, spec: CellSpec):
    """jnp source of truth: the unfused cell (folded-affine form).

    prev, cur: [B, H, W, C_prev] / [B, H, W, C_cur] at the SAME spatial
    resolution (the model's `_reduce_prev_layer` runs upstream). Returns
    [B, H', W', filters * num_unused] in cur's dtype.
    """
    return _cell_body(prev, cur, params, spec).astype(cur.dtype)


# ------------------------------------------------------------------ kernel


def _cell_kernel(*refs, treedef, num_leaves, spec):
    prev_ref, cur_ref = refs[0], refs[1]
    leaves = [r[...] for r in refs[2 : 2 + num_leaves]]
    o_ref = refs[2 + num_leaves]
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    out = _cell_body(prev_ref[...], cur_ref[...], params, spec)
    o_ref[...] = out.astype(o_ref.dtype)


def output_shape(
    spec: CellSpec, batch: int, h: int, w: int, filters: int
) -> Tuple[int, int, int, int]:
    h_out = -(-h // spec.stride)
    w_out = -(-w // spec.stride)
    num_unused = sum(1 for u in spec.used_hiddenstates if not u)
    return (batch, h_out, w_out, filters * num_unused)


def _bytes_per_example(
    spec: CellSpec, h: int, w: int, c_prev: int, c_cur: int, filters: int
) -> int:
    """Conservative f32 VMEM footprint of one example's state list:
    both inputs, every hidden state, and the concat output."""
    num_states = 2 + spec.num_blocks
    num_unused = sum(1 for u in spec.used_hiddenstates if not u)
    return 4 * h * w * (
        c_prev + c_cur + (num_states + num_unused + 1) * filters
    )


def _cell_filters(params) -> int:
    return int(params["begin"]["w"].shape[-1])


def _tune_spec(prev, cur, params, spec: CellSpec) -> Dict[str, Any]:
    return {
        "prev_shape": list(prev.shape),
        "cur_shape": list(cur.shape),
        "dtype": str(cur.dtype),
        "filters": _cell_filters(params),
        "operations": list(spec.operations),
        "hiddenstate_indices": list(spec.hiddenstate_indices),
        "used_hiddenstates": list(spec.used_hiddenstates),
        "stride": spec.stride,
    }


def _pallas_forward(
    prev, cur, params, spec: CellSpec, interpret: bool, block_b=None
):
    b, h, w, _ = cur.shape
    filters = _cell_filters(params)
    if block_b is None:
        per_example = _bytes_per_example(
            spec, h, w, prev.shape[-1], cur.shape[-1], filters
        )
        block_b = max(1, min(b, _VMEM_BUDGET // max(1, per_example)))
        tuned = tuning.lookup("cell", _tune_spec(prev, cur, params, spec))
        if tuned:
            candidate = int(tuned.get("block_b", 0))
            if 0 < candidate <= b and b % candidate == 0:
                block_b = candidate
    while b % block_b:  # grid must tile the batch exactly
        block_b -= 1

    leaves, treedef = jax.tree_util.tree_flatten(params)
    out_shape = output_shape(spec, b, h, w, filters)
    kern = functools.partial(
        _cell_kernel,
        treedef=treedef,
        num_leaves=len(leaves),
        spec=spec,
    )
    in_specs = [
        pl.BlockSpec((block_b, h, w, prev.shape[-1]), lambda i: (i, 0, 0, 0)),
        pl.BlockSpec((block_b, h, w, cur.shape[-1]), lambda i: (i, 0, 0, 0)),
    ]
    for leaf in leaves:
        shape = tuple(leaf.shape)
        in_specs.append(
            pl.BlockSpec(shape, lambda i, nd=len(shape): (0,) * nd)
        )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(out_shape, cur.dtype),
        grid=(b // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_b,) + out_shape[1:], lambda i: (i, 0, 0, 0)
        ),
        interpret=interpret,
    )(prev, cur, *leaves)


def kernel_takes(prev_shape, cur_shape, filters: int, spec: CellSpec) -> bool:
    """The static rule for which cells the Pallas kernel takes.

    - `spec.stride == 1`: a reduction cell needs stride-2 slices of
      VMEM-resident VALUES (`_sepconv_layer`, `_pool`, `_conv1x1`,
      `_factorized_reduction`), which the TPU compiler refuses
      (`vector.extract_strided_slice` takes unit strides only; open item
      in ROADMAP queue A);
    - both inputs at the same spatial resolution (the model resolves a
      mismatch upstream via `_reduce_prev_layer`);
    - one example's state list inside the VMEM budget (the batch axis is
      the only grid dimension).

    A cell inside the rule that the compiler refuses is an error, not a
    fallback (tests/test_chip_compile.py compiles the real widths).
    """
    if spec.stride != 1:
        return False
    if tuple(prev_shape[1:3]) != tuple(cur_shape[1:3]):
        return False
    h, w = cur_shape[1], cur_shape[2]
    per_example = _bytes_per_example(
        spec, h, w, prev_shape[-1], cur_shape[-1], filters
    )
    return per_example <= _VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_cell_p(prev, cur, params, spec, interpret):
    return _pallas_forward(prev, cur, params, spec, interpret)


def _fused_fwd(prev, cur, params, spec, interpret):
    return (
        _pallas_forward(prev, cur, params, spec, interpret),
        (prev, cur, params),
    )


def _fused_bwd(spec, interpret, residuals, g):
    prev, cur, params = residuals
    # Backward via the reference's VJP (one extra forward — the same
    # FLOPs-for-HBM trade as NasNetConfig.remat / fused_sep_conv).
    _, vjp = jax.vjp(
        lambda p, c, par: cell_reference(p, c, par, spec),
        prev,
        cur,
        params,
    )
    return vjp(g)


_fused_cell_p.defvjp(_fused_fwd, _fused_bwd)


def fused_cell(
    prev,
    cur,
    params,
    spec: CellSpec,
    *,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """One NASNet-A cell (folded-affine form), VMEM-resident per tile.

    prev: [B, H, W, C_prev]; cur: [B, H, W, C_cur]; params from
    `init_cell_params`. Returns [B, H', W', filters * num_unused] in
    cur's dtype. Takes the Pallas kernel exactly when the cell is inside
    `kernel_takes` (and `use_pallas`); otherwise the unfused
    `cell_reference`, by that rule and never by a caught compiler error.
    `interpret=True` runs the kernel in interpreter mode (the CPU
    oracle-test path). Platform choice is per lowering platform
    (`jax.lax.platform_dependent`), matching `fused_sep_conv`.
    """
    if not (
        use_pallas
        and kernel_takes(prev.shape, cur.shape, _cell_filters(params), spec)
    ):
        return cell_reference(prev, cur, params, spec)
    if interpret:
        return _fused_cell_p(prev, cur, params, spec, True)
    return jax.lax.platform_dependent(
        prev,
        cur,
        params,
        tpu=lambda p, c, par: _fused_cell_p(p, c, par, spec, False),
        default=lambda p, c, par: cell_reference(p, c, par, spec),
    )
