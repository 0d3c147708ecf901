"""Mamba-2's chunked SSD scan as one Pallas TPU kernel pair.

`ssd` runs the same algorithm as `models.mamba2.ssd_chunked`'s jnp form:
inside a chunk of Q steps the masked quadratic form, across chunks the
carried (head, P, N) state. The forward kernel and the backward kernel
are joined by `jax.custom_vjp`; each chunk's Q x Q scores, decays and the
carried state live only in VMEM.

Layout (kernel side, S padded to a multiple of Q, W = hb * P lanes):

  x, y, dx    (B, S, H*P)        lane-dense, as the projections produce
  dt          (B, nc, H, Q) f32  one row of Q steps per head
  B, C        (B, S, N)          group-shared (G = 1)
  state       (B, H/hb, N, W) f32  transposed: state[n, (h, p)]
  states      (B, nc, H/hb, N, W) f32  chunk-start states, the residual

Grid (batch, chunk, head block): the chunk axis runs in order and the
head-block axis innermost, so C B^T is formed once per chunk and the
gradients of B and C add up over head blocks in VMEM. The state of every
head stays in the resident output block of the final state (forward) or
of d init_state (backward).

Inside a step, heads go in lane groups of g = 128 / P. A group's scores
are held transposed, M^T[(a, k), q] for head a of the group, so that
M x for the g heads is one (g Q, Q)^T @ (g Q, 128) product against the
block-diagonal x. Everything elementwise is float32 (the cumulative sum
of dt A is a HIGHEST-precision product with a triangle of ones); MXU
operands take x's dtype and accumulate in float32. Exponentials are only
taken of differences that are <= 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128
MAX_BLOCK_LANES = 4096         # W = hb * P the kernel aims for
VMEM_LIMIT = 48 * 2 ** 20      # scoped VMEM the kernels may use
_STATE_BUDGET = 24 * 2 ** 20   # the resident state blocks, double-buffered
_F32 = jnp.float32


# --------------------------------------------------------------------------
# geometry and dispatch
# --------------------------------------------------------------------------

def head_block(H: int, P: int, N: int, chunk: int) -> Optional[int]:
    """Heads per block for the compiled kernels, or None where the shapes
    do not tile: P must divide 128 (heads pack whole into lane groups),
    the chunk must fill bf16 sublane tiles, a head block's rows of dt must
    fill f32 sublane tiles (or be every head), and the state of every head
    must fit VMEM."""
    if P > LANES or LANES % P or chunk % 16 or N % 8:
        return None
    if 4 * N * H * P * 4 > _STATE_BUDGET:
        return None
    g = LANES // P
    fits = [hb for hb in range(g, H + 1, g)
            if H % hb == 0 and (hb % 8 == 0 or hb == H)]
    small = [hb for hb in fits if hb * P <= MAX_BLOCK_LANES]
    if small:
        return max(small)
    return min(fits) if fits else None


def _geometry(x, dt_r, hb):
    Bsz, S, HP = x.shape
    _, nc, H, Q = dt_r.shape
    P = HP // H
    g = max(1, LANES // P)
    assert HP == H * P and S == nc * Q and H % hb == 0 and hb % g == 0, \
        (x.shape, dt_r.shape, hb)
    return dict(B=Bsz, S=S, H=H, P=P, Q=Q, nc=nc, hb=hb, g=g,
                nhb=H // hb, W=hb * P, GP=g * P)


# --------------------------------------------------------------------------
# in-kernel helpers (static shapes; the head indices are Python ints)
# --------------------------------------------------------------------------

def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_tn(a, b):
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_exact(a, b, *, nt=False):
    """A float32 product with a 0/1 matrix, at full precision."""
    dims = (((1,), (1,)) if nt else ((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _upper(Q):
    """U[k, q] = 1 where k <= q: cum = dA @ U is the in-chunk cumsum."""
    return (_iota((Q, Q), 0) <= _iota((Q, Q), 1)).astype(_F32)


def _lanes(cols, h0, g, P, rows):
    """(rows, g P): lane (a, p) holds cols[:, h0 + a]."""
    lane = _iota((rows, g * P), 1)
    out = jnp.broadcast_to(cols[:, h0:h0 + 1], (rows, g * P))
    for a in range(1, g):
        out = jnp.where(lane >= a * P, cols[:, h0 + a:h0 + a + 1], out)
    return out


def _stack_cols(cols, h0, g, Q):
    """(g Q, Q): row (a, k) holds cols[k, h0 + a] in every lane."""
    return jnp.concatenate(
        [jnp.broadcast_to(cols[:, h0 + a:h0 + a + 1], (Q, Q))
         for a in range(g)], axis=0)


def _stack_rows(rows, h0, g, Q):
    """(g Q, Q): row (a, k) holds rows[h0 + a, :]."""
    return jnp.concatenate(
        [jnp.broadcast_to(rows[h0 + a:h0 + a + 1, :], (Q, Q))
         for a in range(g)], axis=0)


def _block_diag(x, g, P):
    """x (Q, g P) -> (g Q, g P): head a's lanes in row block a, else 0."""
    lane = _iota(x.shape, 1)
    return jnp.concatenate(
        [jnp.where((lane >= a * P) & (lane < (a + 1) * P), x,
                   jnp.zeros_like(x)) for a in range(g)], axis=0)


def _lane_sums(v, g, P):
    """(rows, g P) -> g columns (rows, 1): the sum over each head's lanes."""
    lane = _iota(v.shape, 1)
    return [jnp.sum(jnp.where((lane >= a * P) & (lane < (a + 1) * P), v, 0.0),
                    axis=1, keepdims=True) for a in range(g)]


def _put_col(acc, h, col):
    """acc (rows, hb) with column h increased by col (rows, 1)."""
    return acc + jnp.where(_iota(acc.shape, 1) == h, col, 0.0)


def _put_row(acc, h, row):
    """acc (hb, Q) with row h increased by row (1, Q)."""
    return acc + jnp.where(_iota(acc.shape, 0) == h, row, 0.0)


def _mask_T(g, Q):
    """(g Q, Q): True where k <= q for row (a, k), lane q."""
    return (_iota((g * Q, Q), 0) % Q) <= _iota((g * Q, Q), 1)


def _chunk_cum(dt_ref, a_ref, Q):
    """-> dt (hb, Q), its cumsum of dt A (hb, Q) and both transposed."""
    dt_r = dt_ref[0, 0]
    cum_r = _dot_exact(dt_r * a_ref[0], _upper(Q))
    return dt_r, cum_r, dt_r.T, cum_r.T


def _scores_T(cbT, cum_r, cum_c, dt_c, h0, g, Q):
    """Group of heads h0.. -> (C B^T o L)^T and M^T (g Q, Q), and dt_k
    stacked the same way. L = exp(cum_q - cum_k) for k <= q, else 0."""
    segT = _stack_rows(cum_r, h0, g, Q) - _stack_cols(cum_c, h0, g, Q)
    LT = jnp.exp(jnp.where(_mask_T(g, Q), segT, -jnp.inf))
    dtS = _stack_cols(dt_c, h0, g, Q)
    cblT = cbT * LT
    return LT, dtS, cblT, cblT * dtS


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, init_ref,
                y_ref, fin_ref, *rest, geo, with_states):
    st_ref = rest[0] if with_states else None
    cb_ref = rest[-1]
    Q, P, g, hb = geo["Q"], geo["P"], geo["g"], geo["hb"]
    GP = geo["GP"]
    c, j = pl.program_id(1), pl.program_id(2)
    bm, cm = b_ref[0], c_ref[0]                              # (Q, N)
    mx = x_ref.dtype

    @pl.when((c == 0) & (j == 0))
    def _():
        fin_ref[...] = init_ref[...]

    @pl.when(j == 0)
    def _():
        cb_ref[...] = _dot_nt(jnp.concatenate([bm] * g, axis=0), cm)

    dt_r, cum_r, dt_c, cum_c = _chunk_cum(dt_ref, a_ref, Q)
    cbT = cb_ref[...]
    x = x_ref[0]                                             # (Q, W)
    s0 = fin_ref[0, j]                                       # (N, W)
    if with_states:
        st_ref[0, 0, 0] = s0
    y_in = _dot(cm, s0.astype(mx))                           # (Q, W)
    for i in range(hb // g):
        h0, L = i * g, slice(i * GP, (i + 1) * GP)
        xi = x[:, L]
        _, _, _, mT = _scores_T(cbT, cum_r, cum_c, dt_c, h0, g, Q)
        y = _dot_tn(mT.astype(mx), _block_diag(xi, g, P))
        cumP = _lanes(cum_c, h0, g, P, Q)
        lastP = cumP[Q - 1:Q, :]
        xf = xi.astype(_F32)
        y = y + y_in[:, L] * jnp.exp(cumP) + d_ref[:, L] * xf
        y_ref[0, :, L] = y.astype(y_ref.dtype)
        xw = xf * (jnp.exp(lastP - cumP) * _lanes(dt_c, h0, g, P, Q))
        fin_ref[0, j, :, L] = (jnp.exp(lastP) * s0[:, L]
                               + _dot_tn(bm, xw.astype(mx)))


def _fwd_call(x, dt_r, a, bm, cm, dl, init, *, hb, with_states, interpret):
    geo = _geometry(x, dt_r, hb)
    Bsz, nc, nhb, Q, N = geo["B"], geo["nc"], geo["nhb"], geo["Q"], \
        bm.shape[-1]
    W, g = geo["W"], geo["g"]
    full_state = pl.BlockSpec((1, nhb, N, W), lambda b, c, j: (b, 0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct((Bsz, nhb, N, W), _F32)]
    out_specs = [pl.BlockSpec((1, Q, W), lambda b, c, j: (b, c, j)),
                 full_state]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((Bsz, nc, nhb, N, W), _F32))
        out_specs.append(pl.BlockSpec((1, 1, 1, N, W),
                                      lambda b, c, j: (b, c, j, 0, 0)))
    name = "ssd_fwd_states" if with_states else "ssd_fwd"
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, geo=geo, with_states=with_states),
        grid=(Bsz, nc, nhb),
        in_specs=[
            pl.BlockSpec((1, Q, W), lambda b, c, j: (b, c, j)),
            pl.BlockSpec((1, 1, hb, Q), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, hb, 1), lambda b, c, j: (j, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((1, W), lambda b, c, j: (0, j)),
            full_state,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g * Q, Q), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )
    with jax.named_scope(name):
        return call(x, dt_r, a, bm, cm, dl, init)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_kernel(x_ref, gy_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, st_ref,
                dfin_ref, dx_ref, ddt_ref, dat_ref, db_ref, dc_ref, dd_ref,
                dinit_ref, cb_ref, dcb_ref, *, geo):
    Q, P, g, hb, nhb = geo["Q"], geo["P"], geo["g"], geo["hb"], geo["nhb"]
    GP = geo["GP"]
    t, j = pl.program_id(1), pl.program_id(2)
    bm, cm = b_ref[0], c_ref[0]                              # (Q, N)
    mx = x_ref.dtype
    b_stack = jnp.concatenate([bm] * g, axis=0)              # (g Q, N)

    @pl.when((t == 0) & (j == 0))
    def _():
        dinit_ref[...] = dfin_ref[...]

    @pl.when(j == 0)
    def _():
        cb_ref[...] = _dot_nt(b_stack, cm)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

    dt_r, cum_r, dt_c, cum_c = _chunk_cum(dt_ref, a_ref, Q)
    cbT = cb_ref[...]
    x, gy = x_ref[0], gy_ref[0]                              # (Q, W)
    s0, ds1 = st_ref[0, 0, 0], dinit_ref[0, j]               # (N, W)
    c_s0 = _dot(cm, s0.astype(mx))                           # (Q, W)
    b_ds = _dot(bm, ds1.astype(mx))                          # (Q, W)
    dcum_r = jnp.zeros((hb, Q), _F32)
    dcum_c = jnp.zeros((Q, hb), _F32)
    ddt_c = jnp.zeros((Q, hb), _F32)
    dlast = jnp.zeros((1, hb), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    dcbT = jnp.zeros((g * Q, Q), _F32)
    for i in range(hb // g):
        h0, L = i * g, slice(i * GP, (i + 1) * GP)
        xi, gi = x[:, L], gy[:, L]
        xf, gf = xi.astype(_F32), gi.astype(_F32)
        LT, dtS, cblT, mT = _scores_T(cbT, cum_r, cum_c, dt_c, h0, g, Q)
        cumP = _lanes(cum_c, h0, g, P, Q)
        lastP = cumP[Q - 1:Q, :]
        sc, dec = jnp.exp(cumP), jnp.exp(lastP)
        e_w = jnp.exp(lastP - cumP)
        wP = e_w * _lanes(dt_c, h0, g, P, Q)

        # inside the chunk: y += M x
        dmT = _dot_nt(_block_diag(xi, g, P), gi)             # (g Q, Q)
        dxbd = _dot(mT.astype(mx), gi)                       # (g Q, g P)
        lane = _iota((Q, GP), 1)
        dx = sum(jnp.where((lane >= a * P) & (lane < (a + 1) * P),
                           dxbd[a * Q:(a + 1) * Q], 0.0) for a in range(g))
        t1 = dmT * cblT                                      # d dt_k terms
        dcbT = dcbT + dmT * LT * dtS
        dseg = t1 * dtS                                      # dM o M
        for a in range(g):
            rows = slice(a * Q, (a + 1) * Q)
            dcum_r = _put_row(dcum_r, h0 + a,
                              jnp.sum(dseg[rows], axis=0, keepdims=True))
            dcum_c = _put_col(dcum_c, h0 + a,
                              -jnp.sum(dseg[rows], axis=1, keepdims=True))
            ddt_c = _put_col(ddt_c, h0 + a,
                             jnp.sum(t1[rows], axis=1, keepdims=True))

        # the carried state's output: y += (C s0^T) o exp(cum_q)
        gsc = gf * sc
        dy_in = _lane_sums(gsc * c_s0[:, L], g, P)
        dc = dc + _dot_nt(gsc.astype(mx), s0[:, L].astype(mx))
        ds0 = dec * ds1[:, L] + _dot_tn(cm, gsc.astype(mx))

        # the chunk's input to the state: s1 += B^T (x o w), w = e_w dt
        dwP = xf * b_ds[:, L]
        db = db + _dot_nt((xf * wP).astype(mx), ds1[:, L].astype(mx))
        dx = dx + wP * b_ds[:, L] + d_ref[:, L] * gf
        tw = _lane_sums(dwP * wP, g, P)
        dte = _lane_sums(dwP * e_w, g, P)
        dkeep = _lane_sums(jnp.sum(ds1[:, L] * s0[:, L], axis=0,
                                   keepdims=True) * dec, g, P)
        for a in range(g):
            h = h0 + a
            dcum_c = _put_col(dcum_c, h, dy_in[a] - tw[a])
            ddt_c = _put_col(ddt_c, h, dte[a])
            dlast = _put_col(dlast, h, jnp.sum(tw[a], axis=0, keepdims=True)
                             + dkeep[a])

        dx_ref[0, :, L] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, :, L] = jnp.sum(gf * xf, axis=0, keepdims=True)
        dinit_ref[0, j, :, L] = ds0

    dcum_c = dcum_c + jnp.where(_iota((Q, hb), 0) == Q - 1, dlast, 0.0)
    dcum = dcum_r + dcum_c.T                                 # (hb, Q)
    d_da = _dot_exact(dcum, _upper(Q), nt=True)              # reverse cumsum
    ddt_ref[0, 0] = ddt_c.T + d_da * a_ref[0]
    dat_ref[0, 0] = d_da * dt_r
    dcb_ref[...] += dcbT

    @pl.when(j == 0)
    def _():
        db_ref[0] = db
        dc_ref[0] = dc

    @pl.when(j > 0)
    def _():
        db_ref[0] += db
        dc_ref[0] += dc

    @pl.when(j == nhb - 1)
    def _():
        dcb = dcb_ref[...].astype(mx)
        db_stack = _dot(dcb, cm)                             # (g Q, N)
        db_ref[0] += sum(db_stack[a * Q:(a + 1) * Q] for a in range(g))
        dc_ref[0] += _dot_tn(dcb, b_stack)


def _bwd_call(x, dt_r, a, bm, cm, dl, states, gy, dfin, *, hb, interpret):
    geo = _geometry(x, dt_r, hb)
    Bsz, nc, nhb, Q, N = geo["B"], geo["nc"], geo["nhb"], geo["Q"], \
        bm.shape[-1]
    W, g, H = geo["W"], geo["g"], geo["H"]

    def rev(f):
        return lambda b, t, j: f(b, nc - 1 - t, j)
    full_state = pl.BlockSpec((1, nhb, N, W), lambda b, t, j: (b, 0, 0, 0))
    seq_w = pl.BlockSpec((1, Q, W), rev(lambda b, c, j: (b, c, j)))
    heads = pl.BlockSpec((1, 1, hb, Q), rev(lambda b, c, j: (b, c, j, 0)))
    seq_n = pl.BlockSpec((1, Q, N), rev(lambda b, c, j: (b, c, 0)))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, geo=geo),
        grid=(Bsz, nc, nhb),
        in_specs=[
            seq_w, seq_w, heads,
            pl.BlockSpec((1, hb, 1), lambda b, t, j: (j, 0, 0)),
            seq_n, seq_n,
            pl.BlockSpec((1, W), lambda b, t, j: (0, j)),
            pl.BlockSpec((1, 1, 1, N, W),
                         rev(lambda b, c, j: (b, c, j, 0, 0))),
            full_state,
        ],
        out_specs=[
            seq_w, heads, heads, seq_n, seq_n,
            pl.BlockSpec((1, 1, 1, W), rev(lambda b, c, j: (b, c, 0, j))),
            full_state,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bsz, nc, H, Q), _F32),
            jax.ShapeDtypeStruct((Bsz, nc, H, Q), _F32),
            jax.ShapeDtypeStruct(bm.shape, _F32),
            jax.ShapeDtypeStruct(cm.shape, _F32),
            jax.ShapeDtypeStruct((Bsz, nc, 1, H * geo["P"]), _F32),
            jax.ShapeDtypeStruct((Bsz, nhb, N, W), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((g * Q, Q), _F32),
                        pltpu.VMEM((g * Q, Q), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_bwd",
    )
    with jax.named_scope("ssd_bwd"):
        return call(x, gy, dt_r, a, bm, cm, dl, states, dfin)


# --------------------------------------------------------------------------
# the differentiable pair, in kernel layout
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _ssd_core(x, dt_r, a, bm, cm, dl, init, hb, interpret):
    y, fin = _fwd_call(x, dt_r, a, bm, cm, dl, init, hb=hb,
                       with_states=False, interpret=interpret)
    return y, fin


def _ssd_core_fwd(x, dt_r, a, bm, cm, dl, init, hb, interpret):
    y, fin, states = _fwd_call(x, dt_r, a, bm, cm, dl, init, hb=hb,
                               with_states=True, interpret=interpret)
    return (y, fin), (x, dt_r, a, bm, cm, dl, states)


def _ssd_core_bwd(hb, interpret, res, cts):
    x, dt_r, a, bm, cm, dl, states = res
    gy, dfin = cts
    dx, ddt, dat, db, dc, dd, dinit = _bwd_call(
        x, dt_r, a, bm, cm, dl, states, gy.astype(x.dtype),
        dfin.astype(_F32), hb=hb, interpret=interpret)
    da = jnp.sum(dat, axis=(0, 1, 3)).reshape(a.shape)
    return (dx, ddt, da, db.astype(bm.dtype), dc.astype(cm.dtype),
            jnp.sum(dd, axis=(0, 1)), dinit)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd(x: Array, dt: Array, A: Array, Bm: Array, Cm: Array, D: Array,
        chunk: int, init_state: Optional[Array] = None, *, hb: int,
        interpret: bool):
    """Chunked SSD scan through the kernel pair.

    x (B, S, H*P) values, lane-dense; dt (B, S, H) softplus'd step; A (H,)
    negative; Bm/Cm (B, S, N) group-shared projections; D (H,) skip;
    init_state (B, H, P, N) or None. hb heads per block (`head_block`).
    Returns (y (B, S, H*P) in x's dtype, final_state (B, H, P, N) f32).
    """
    Bsz, S, HP = x.shape
    H, N = dt.shape[-1], Bm.shape[-1]
    P = HP // H
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc, nhb = (S + pad) // chunk, H // hb
    dt_r = dt.astype(_F32).reshape(Bsz, nc, chunk, H).transpose(0, 1, 3, 2)
    a = A.astype(_F32).reshape(nhb, hb, 1)
    dl = jnp.repeat(D.astype(_F32), P)[None, :]
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), _F32)
    init = init_state.astype(_F32).reshape(Bsz, nhb, hb, P, N) \
        .transpose(0, 1, 4, 2, 3).reshape(Bsz, nhb, N, hb * P)
    y, fin = _ssd_core(x, dt_r, a, Bm, Cm, dl, init, hb, interpret)
    final = fin.reshape(Bsz, nhb, N, hb, P).transpose(0, 1, 3, 4, 2) \
        .reshape(Bsz, H, P, N)
    return y[:, :S], final
