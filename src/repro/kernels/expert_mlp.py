"""Fused grouped expert-FFN Pallas TPU kernel, forward + custom-VJP backward.

Computes, for every expert e:   y[e] = act(x[e] @ wi[e]) [* (x[e] @ wg[e])] @ wo[e]
with xe: (E, cap, d), wi/wg: (E, d, f), wo: (E, f, d) — the MoE hot-spot
(both matmuls + activation fused; the (cap, f) hidden tensor never leaves
VMEM).

Forward tiling: grid (E, cap/bc, f/bf, d/bd), d innermost. The first matmul
accumulates h[bc, bf] into a VMEM scratch over d tiles; at the last d tile
the activation fires and the second matmul accumulates into the output
block (revisited across f tiles — consecutive grid iterations, the
standard Pallas accumulation pattern). VMEM working set per step:
bc*bd + 2*bd*bf + bf*bd + 2*bc*bf + bc*bd floats — with the default
(bc, bf, bd) = (128, 256, 512) about 2.3 MB, comfortably under the 16 MB
v5e VMEM budget, and every MXU dim is a multiple of 128.

Backward (``expert_ffn_pallas_vjp``): residuals are the *inputs only*
(xe, wi, wg, wo) — the (cap, f) pre-activations are recomputed in-kernel,
so the VJP's memory high-water mark is the same as the forward's. Two
fused grouped kernels, each keeping every (cap, f) hidden/grad tensor in
VMEM:

* dx kernel — grid (E, cap/bc, f/bf, 2*d/bd), two phases over the last
  axis. Phase 1 (t < nd) re-accumulates a = x@wi, g = x@wg and
  dh = dy@wo^T over d tiles; at t == nd the activation VJP turns (a, g,
  dh) into (da, dg) in-place in scratch; phase 2 (t >= nd) sweeps d tiles
  again, accumulating dx[:, d-tile] += da@wi^T + dg@wg^T into a (bc, d)
  f32 scratch that persists across f tiles and is flushed to the output
  on the last (f, t) step.
* dW kernel — grid (E, f/bf, cap/bc), cap innermost. Each step recomputes
  (a, g, dh) for one (bc, bf) tile from full-d x/dy rows and accumulates
  dwi += x^T@da, dwg += x^T@dg, dwo += h^T@dy into f32 VMEM scratch,
  flushed to the outputs on the last cap step (the revisited-block
  pattern, but with explicit f32 accumulators so low-precision outputs
  don't lose the summation).

See src/repro/kernels/README.md for the per-kernel VMEM budgets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    check_mxu_alignment,
    clamp_tile,
    tune_expert_tiles,
    VMEM_LIMIT_BYTES,
)

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _act_fn(name: str):
    from repro.models.layers import activation

    return activation(name)


def _clamp_tiles(bc, bf, bd, cap, f, d, interpret):
    """Tile sizes default (None) to the VMEM budget model in tiling.py —
    (128, 256, 512) for small d_model, bf=128 from d_model >= 4096.
    Interpret: tiles shrink to the dims (tiny test shapes). Compiled:
    tiles clamp to the 128-aligned ceiling — small cap/f/d zero-pad up to
    one MXU tile — and explicitly misaligned tiles raise."""
    if bc is None or bf is None or bd is None:
        tc, tf, td = tune_expert_tiles(cap, f, d)
        bc = tc if bc is None else bc
        bf = tf if bf is None else bf
        bd = td if bd is None else bd
    bc = clamp_tile(bc, cap, interpret)
    bf = clamp_tile(bf, f, interpret)
    bd = clamp_tile(bd, d, interpret)
    check_mxu_alignment("expert FFN", interpret, bc=bc, bf=bf, bd=bd)
    return bc, bf, bd


def _kernel(x_ref, wi_ref, wg_ref, wo_ref, o_ref, h_acc, g_acc, *,
            act: str, nd: int, nf: int):
    di = pl.program_id(3)
    fi = pl.program_id(2)

    @pl.when(di == 0)
    def _():
        h_acc[...] = jnp.zeros_like(h_acc)
        if g_acc is not None:
            g_acc[...] = jnp.zeros_like(g_acc)

    x = x_ref[0]  # (bc, bd)
    h_acc[...] += jnp.dot(
        x, wi_ref[0], preferred_element_type=jnp.float32
    )
    if g_acc is not None:
        g_acc[...] += jnp.dot(
            x, wg_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(di == nd - 1)
    def _():
        h = _act_fn(act)(h_acc[...])
        if g_acc is not None:
            h = h * g_acc[...]
        y = jnp.dot(
            h.astype(wo_ref.dtype), wo_ref[0],
            preferred_element_type=jnp.float32,
        )

        @pl.when(fi == 0)
        def _():
            o_ref[0] = y.astype(o_ref.dtype)

        @pl.when(fi != 0)
        def _():
            o_ref[0] = (o_ref[0].astype(jnp.float32) + y).astype(o_ref.dtype)


def _pad_inputs(xe, wi, wg, wo, bc, bf, bd):
    E, cap, d = xe.shape
    f = wi.shape[-1]
    pc, pf, pd = (-cap) % bc, (-f) % bf, (-d) % bd
    if pc or pd:
        xe = jnp.pad(xe, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        wi = jnp.pad(wi, ((0, 0), (0, pd), (0, pf)))
        if wg is not None:
            wg = jnp.pad(wg, ((0, 0), (0, pd), (0, pf)))
        wo = jnp.pad(wo, ((0, 0), (0, pf), (0, pd)))
    return xe, wi, wg, wo, pc, pf, pd


@functools.partial(
    jax.jit,
    static_argnames=("act", "bc", "bf", "bd", "interpret"),
)
def expert_ffn_pallas(
    xe, wi, wg, wo, *, act: str = "silu",
    bc=None, bf=None, bd=None,
    interpret: bool = False,
):
    """xe: (E, cap, d) -> (E, cap, d). Forward only (no VJP registered —
    use ``expert_ffn_pallas_vjp`` for anything under ``jax.grad``)."""
    E, cap, d = xe.shape
    f = wi.shape[-1]
    bc, bf, bd = _clamp_tiles(bc, bf, bd, cap, f, d, interpret)
    # pad to tile multiples (zero rows are harmless: act(0)*0 etc. — but
    # note sqrelu(0)=0 and silu(0)=0, gelu(0)=0, so padded rows stay 0)
    xe, wi, wg, wo, pc, pf, pd = _pad_inputs(xe, wi, wg, wo, bc, bf, bd)
    capp, fp, dp = cap + pc, f + pf, d + pd
    nc, nf, nd = capp // bc, fp // bf, dp // bd
    gated = wg is not None

    grid = (E, nc, nf, nd)
    in_specs = [
        pl.BlockSpec((1, bc, bd), lambda e, c, fi, di: (e, c, di)),
        pl.BlockSpec((1, bd, bf), lambda e, c, fi, di: (e, di, fi)),
    ]
    args = [xe, wi]
    if gated:
        in_specs.append(
            pl.BlockSpec((1, bd, bf), lambda e, c, fi, di: (e, di, fi))
        )
        args.append(wg)
    # wo tile and the output block span the FULL d dim: the second matmul
    # produces all d columns for each (cap, f) tile, accumulated over f.
    in_specs.append(
        pl.BlockSpec((1, bf, dp), lambda e, c, fi, di: (e, fi, 0))
    )
    args.append(wo)

    scratch = [pltpu.VMEM((bc, bf), jnp.float32)]
    if gated:
        scratch.append(pltpu.VMEM((bc, bf), jnp.float32))

    def kernel(*refs):
        if gated:
            x_ref, wi_ref, wg_ref, wo_ref, o_ref, h_acc, g_acc = refs
        else:
            x_ref, wi_ref, wo_ref, o_ref, h_acc = refs
            wg_ref = g_acc = None
        _kernel(x_ref, wi_ref, wg_ref, wo_ref, o_ref, h_acc, g_acc,
                act=act, nd=nd, nf=nf)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, dp), lambda e, c, fi, di: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, capp, dp), xe.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    if pc or pd:
        out = out[:, :cap, :d]
    return out


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_grads_f_tile(x, dy, wi_t, wg_t, wo_t, act):
    """One (bc, bf) tile of the hidden-space gradients, from full-d rows.

    Returns (h, da, dg): the post-activation hidden (for dwo) and the
    pre-activation gradients (for dwi/dwg/dx). dg is None when ungated.
    """
    a = jnp.dot(x, wi_t, preferred_element_type=jnp.float32)
    dh = jax.lax.dot_general(  # dy @ wo_t^T
        dy, wo_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    act_out, act_vjp = jax.vjp(_act_fn(act), a)
    if wg_t is not None:
        g = jnp.dot(x, wg_t, preferred_element_type=jnp.float32)
        h = act_out * g
        da = act_vjp(dh * g)[0]
        dg = dh * act_out
    else:
        h = act_out
        da = act_vjp(dh)[0]
        dg = None
    return h, da, dg


def _dx_kernel(x_ref, wi_ref, wg_ref, wo_ref, dy_ref, dx_ref,
               a_acc, g_acc, dh_acc, dx_acc, *,
               act: str, nd: int, nf: int, bd: int):
    """Phase 1 (t < nd): accumulate a, g, dh over d tiles. Phase 2
    (t >= nd): activation VJP once, then expand da/dg back to d tiles,
    accumulating into the persistent (bc, dp) dx scratch."""
    fi = pl.program_id(2)
    t = pl.program_id(3)
    di = jax.lax.rem(t, nd)

    @pl.when((fi == 0) & (t == 0))
    def _():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    @pl.when(t == 0)
    def _():
        a_acc[...] = jnp.zeros_like(a_acc)
        dh_acc[...] = jnp.zeros_like(dh_acc)
        if g_acc is not None:
            g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(t < nd)
    def _():
        x = x_ref[0]  # (bc, bd)
        a_acc[...] += jnp.dot(
            x, wi_ref[0], preferred_element_type=jnp.float32
        )
        if g_acc is not None:
            g_acc[...] += jnp.dot(
                x, wg_ref[0], preferred_element_type=jnp.float32
            )
        dh_acc[...] += jax.lax.dot_general(  # dy @ wo_tile^T -> (bc, bf)
            dy_ref[0], wo_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(t == nd)
    def _():
        # Activation VJP, once per (c, f) tile; overwrite the a/g scratch
        # with da/dg (their phase-1 contents are dead from here on).
        a, dh = a_acc[...], dh_acc[...]
        act_out, act_vjp = jax.vjp(_act_fn(act), a)
        if g_acc is not None:
            g = g_acc[...]
            a_acc[...] = act_vjp(dh * g)[0]
            g_acc[...] = dh * act_out
        else:
            a_acc[...] = act_vjp(dh)[0]

    @pl.when(t >= nd)
    def _():
        da = a_acc[...]
        contrib = jax.lax.dot_general(  # da @ wi_tile^T -> (bc, bd)
            da, wi_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if g_acc is not None:
            contrib += jax.lax.dot_general(
                g_acc[...], wg_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dx_acc[:, pl.ds(di * bd, bd)] += contrib

    # The (e, c) output block is one full-d window (same discipline as the
    # forward's out_spec): its index is constant across all (fi, t) steps,
    # so it stays resident in VMEM and is DMA'd to HBM exactly once, after
    # the single write below on the last step.
    @pl.when((fi == nf - 1) & (t == 2 * nd - 1))
    def _():
        dx_ref[0] = dx_acc[...].astype(dx_ref.dtype)


def _dw_kernel(x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
               dwi_ref, dwg_ref, dwo_ref,
               dwi_acc, dwg_acc, dwo_acc, *, act: str, nc: int):
    """Per step: recompute one (bc, bf) hidden tile from full-d x/dy rows
    and fold it into the f32 dW accumulators; flush on the last cap step."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        dwi_acc[...] = jnp.zeros_like(dwi_acc)
        dwo_acc[...] = jnp.zeros_like(dwo_acc)
        if dwg_acc is not None:
            dwg_acc[...] = jnp.zeros_like(dwg_acc)

    x = x_ref[0]  # (bc, dp)
    dy = dy_ref[0]  # (bc, dp)
    h, da, dg = _recompute_grads_f_tile(
        x, dy, wi_ref[0], wg_ref[0] if wg_ref is not None else None,
        wo_ref[0], act,
    )
    xt_dot = functools.partial(
        jax.lax.dot_general,  # x^T @ grad -> (dp, bf)
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dwi_acc[...] += xt_dot(x, da)
    if dwg_acc is not None:
        dwg_acc[...] += xt_dot(x, dg)
    dwo_acc[...] += xt_dot(h, dy.astype(jnp.float32))  # h^T @ dy -> (bf, dp)

    @pl.when(ci == nc - 1)
    def _():
        dwi_ref[0] = dwi_acc[...].astype(dwi_ref.dtype)
        dwo_ref[0] = dwo_acc[...].astype(dwo_ref.dtype)
        if dwg_acc is not None:
            dwg_ref[0] = dwg_acc[...].astype(dwg_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "bc", "bf", "bd", "interpret"),
)
def _expert_ffn_pallas_bwd(xe, wi, wg, wo, dy, *, act: str,
                           bc, bf, bd, interpret: bool):
    """Returns (dx, dwi, dwg, dwo); dwg is None when wg is None."""
    E, cap, d = xe.shape
    f = wi.shape[-1]
    bc, bf, bd = _clamp_tiles(bc, bf, bd, cap, f, d, interpret)
    xe, wi, wg, wo, pc, pf, pd = _pad_inputs(xe, wi, wg, wo, bc, bf, bd)
    if pc or pd:
        dy = jnp.pad(dy, ((0, 0), (0, pc), (0, pd)))
    capp, fp, dp = cap + pc, f + pf, d + pd
    nc, nf, nd = capp // bc, fp // bf, dp // bd
    gated = wg is not None

    # ---- dx: grid (E, nc, nf, 2*nd), two-phase over the last axis -------
    di_of = lambda t, nd=nd: jax.lax.rem(t, nd)
    in_specs = [
        pl.BlockSpec((1, bc, bd), lambda e, c, fi, t: (e, c, di_of(t))),
        pl.BlockSpec((1, bd, bf), lambda e, c, fi, t: (e, di_of(t), fi)),
    ]
    args = [xe, wi]
    if gated:
        in_specs.append(
            pl.BlockSpec((1, bd, bf), lambda e, c, fi, t: (e, di_of(t), fi))
        )
        args.append(wg)
    in_specs.append(
        pl.BlockSpec((1, bf, bd), lambda e, c, fi, t: (e, fi, di_of(t)))
    )
    args.append(wo)
    in_specs.append(
        pl.BlockSpec((1, bc, bd), lambda e, c, fi, t: (e, c, di_of(t)))
    )
    args.append(dy)

    scratch = [
        pltpu.VMEM((bc, bf), jnp.float32),  # a (phase 1) / da (phase 2)
        pltpu.VMEM((bc, bf), jnp.float32),  # dh
        pltpu.VMEM((bc, dp), jnp.float32),  # dx accumulator (across f)
    ]
    if gated:
        scratch.insert(1, pltpu.VMEM((bc, bf), jnp.float32))  # g / dg

    def dx_kernel(*refs):
        if gated:
            (x_ref, wi_ref, wg_ref, wo_ref, dy_ref, dx_ref,
             a_acc, g_acc, dh_acc, dx_acc) = refs
        else:
            (x_ref, wi_ref, wo_ref, dy_ref, dx_ref,
             a_acc, dh_acc, dx_acc) = refs
            wg_ref = g_acc = None
        _dx_kernel(x_ref, wi_ref, wg_ref, wo_ref, dy_ref, dx_ref,
                   a_acc, g_acc, dh_acc, dx_acc,
                   act=act, nd=nd, nf=nf, bd=bd)

    dx = pl.pallas_call(
        dx_kernel,
        grid=(E, nc, nf, 2 * nd),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, dp), lambda e, c, fi, t: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, capp, dp), xe.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)

    # ---- dW: grid (E, nf, nc), cap innermost ----------------------------
    in_specs = [
        pl.BlockSpec((1, bc, dp), lambda e, fi, c: (e, c, 0)),
        pl.BlockSpec((1, dp, bf), lambda e, fi, c: (e, 0, fi)),
    ]
    args = [xe, wi]
    if gated:
        in_specs.append(
            pl.BlockSpec((1, dp, bf), lambda e, fi, c: (e, 0, fi))
        )
        args.append(wg)
    in_specs.append(
        pl.BlockSpec((1, bf, dp), lambda e, fi, c: (e, fi, 0))
    )
    args.append(wo)
    in_specs.append(
        pl.BlockSpec((1, bc, dp), lambda e, fi, c: (e, c, 0))
    )
    args.append(dy)

    out_specs = [
        pl.BlockSpec((1, dp, bf), lambda e, fi, c: (e, 0, fi)),
        pl.BlockSpec((1, bf, dp), lambda e, fi, c: (e, fi, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((E, dp, fp), wi.dtype),
        jax.ShapeDtypeStruct((E, fp, dp), wo.dtype),
    ]
    scratch = [
        pltpu.VMEM((dp, bf), jnp.float32),  # dwi
        pltpu.VMEM((bf, dp), jnp.float32),  # dwo
    ]
    if gated:
        out_specs.insert(
            1, pl.BlockSpec((1, dp, bf), lambda e, fi, c: (e, 0, fi))
        )
        out_shape.insert(1, jax.ShapeDtypeStruct((E, dp, fp), wg.dtype))
        scratch.insert(1, pltpu.VMEM((dp, bf), jnp.float32))

    def dw_kernel(*refs):
        if gated:
            (x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
             dwi_ref, dwg_ref, dwo_ref,
             dwi_acc, dwg_acc, dwo_acc) = refs
        else:
            (x_ref, wi_ref, wo_ref, dy_ref,
             dwi_ref, dwo_ref, dwi_acc, dwo_acc) = refs
            wg_ref = dwg_ref = dwg_acc = None
        _dw_kernel(x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
                   dwi_ref, dwg_ref, dwo_ref,
                   dwi_acc, dwg_acc, dwo_acc, act=act, nc=nc)

    dws = pl.pallas_call(
        dw_kernel,
        grid=(E, nf, nc),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    if gated:
        dwi, dwg, dwo = dws
    else:
        dwi, dwo = dws
        dwg = None

    if pc or pd:
        dx = dx[:, :cap, :d]
    if pd or pf:
        dwi = dwi[:, :d, :f]
        dwo = dwo[:, :f, :d]
        if gated:
            dwg = dwg[:, :d, :f]
    return dx, dwi, dwg, dwo


@functools.lru_cache(maxsize=None)
def _make_expert_ffn_vjp(act: str, bc, bf, bd,
                         interpret: bool, gated: bool):
    kw = dict(act=act, bc=bc, bf=bf, bd=bd, interpret=interpret)

    if gated:
        @jax.custom_vjp
        def fn(xe, wi, wg, wo):
            return expert_ffn_pallas(xe, wi, wg, wo, **kw)

        def fwd(xe, wi, wg, wo):
            return fn(xe, wi, wg, wo), (xe, wi, wg, wo)

        def bwd(res, dy):
            xe, wi, wg, wo = res
            dx, dwi, dwg, dwo = _expert_ffn_pallas_bwd(
                xe, wi, wg, wo, dy, **kw
            )
            return dx, dwi, dwg, dwo
    else:
        @jax.custom_vjp
        def fn(xe, wi, wo):
            return expert_ffn_pallas(xe, wi, None, wo, **kw)

        def fwd(xe, wi, wo):
            return fn(xe, wi, wo), (xe, wi, wo)

        def bwd(res, dy):
            xe, wi, wo = res
            dx, dwi, _, dwo = _expert_ffn_pallas_bwd(
                xe, wi, None, wo, dy, **kw
            )
            return dx, dwi, dwo

    fn.defvjp(fwd, bwd)
    return fn


def expert_ffn_pallas_vjp(
    xe, wi, wg, wo, *, act: str = "silu",
    bc=None, bf=None, bd=None,
    interpret: bool = False,
):
    """Differentiable fused expert FFN: the forward Pallas kernel with a
    custom VJP whose backward is itself kernel-fused. Drop-in for
    ``expert_ffn_pallas`` anywhere gradients may flow."""
    fn = _make_expert_ffn_vjp(act, bc, bf, bd, bool(interpret),
                              wg is not None)
    if wg is None:
        return fn(xe, wi, wo)
    return fn(xe, wi, wg, wo)
