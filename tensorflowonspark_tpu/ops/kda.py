"""KDA: the chunked gated delta rule of Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692; the chunked delta rule of Yang et al., arXiv:2406.06484),
forward and backward as Pallas kernels.

A head of D key and D value lanes keeps a state S [D, D] and runs, with a
decay per key channel `exp(g_t)` (g <= 0) and a write strength `beta_t`:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(D)

The sequence is cut into chunks of C positions.  With G the chunk's
inclusive running sum of g (per channel, non-increasing; the kernels sum a
chunk's rows themselves, in log2(C) shifted adds, where XLA's running sum
along a row of 128 is a window sum of 127 adds an element), S the state at
the chunk's start, kb = beta k and vb = beta v:

    Akk[t, s] = sum_c kb_tc k_sc exp(G_tc - G_sc)     (s < t)
    Aqk[t, s] = sum_c q_tc  k_sc exp(G_tc - G_sc)     (s <= t)
    T = (I + Akk)^-1                                  (the WY / UT form)
    W = T (kb o exp(G)),  u = T vb - W S
    o = (q o exp(G)) S + Aqk u                        (times 1 / sqrt(D))
    S' = Diag(exp(G_C)) S + (k o exp(G_C - G))^T u

No factor here is the exponential of a positive sum: `exp(G_t - G_s)`
cannot be taken apart as `exp(G_t) exp(-G_s)` (at the decays a trained
layer reaches, `exp(-G)` overflows float32 within a chunk).  So the pairs
of a chunk go by sub-chunks of SUB positions: a pair in two sub-chunks
factors through the first position r of the later one,
`exp(G_t - G_r) exp(G_r - G_s)` with t >= r > s, both factors at most one,
a product on the MXU; a pair in one sub-chunk is summed over its channels
elementwise, a column of the sub-chunks at a time.  T is the inverse of a
unit lower triangular matrix: of its diagonal sub-chunk blocks by the
product (I - N)(I + N^2)(I + N^4)..., then of the rest from those.

`kda_fwd` walks the chunks of a (row, head) in order on the last grid axis,
the state in VMEM, and writes each chunk's starting state (`f32[B, H,
chunks, D, D]`); `kda_bwd` walks them in reverse, computes each chunk's
forward again from its inputs and its starting state, and carries the
state's gradient the same way.  The forward rule names its output `kda_out`
and the chunk states `kda_states` (`jax.ad_checkpoint.checkpoint_name`): a
rematerialised block whose policy saves them
(`models.transformer.remat_block`) does not run `kda_fwd` again; the
backward reads the raw operands, which the block recomputes anyway.

The kernels read q, k, v and g as the convs and the decay lay them out,
`[B, S, H * D]`, a chunk of head h the block `(1, C, D)` at lane tile h,
and beta as a row of `[B, H, 1, S]`, turned into a column in VMEM.  Each
row-local step around the recurrence is theirs: the L2 norms of q and k,
`beta k` and `beta v` (all float32, never rounded in between), and the
head's RMSNorm and weight on the output, whose un-normalised value the
backward computes again from the chunk's forward.  The backward carries
the gradients back through the norm, the recurrence, beta and the L2 norms
and writes dq, dk, dv, dg in the same layout, dbeta as a row and the
norm weight's gradient summed over a (row, head)'s chunks.  So no view a
head and no heads-major copy stands between the projections and the
kernels.  Taking the operands heads-major, `[B, H, S, D]`, cost a
transpose each way around every call; reading them in this layout with the
norms and beta left to XLA cost two relayouts of its view a head
(`[B, S, H, D]`) an operand instead, more than the transposes.  With the
norms inside, on one TPU v5e at the cell's shape of
`benchmark/configs/kimi-linear-48b-a3b.json`, the mixers' XLA work
outside the kernels fell from 320.0 to 200.9 ms a step and the step from
834 to 731 ms, the kernels rising from 142.7 to 159.0.

q, k and v come in float32, the convs' output as XLA computes it, and o,
dq, dk and dv go out in the activations' type (`dtype`).  An f32 product
in these kernels is one bf16 pass of the MXU (Mosaic's default precision),
which rounds the normalised q and k and `beta k`, `beta v` once; the
convs' output rounded to bf16 before the norms as well put the cell's
training on the chip several times further from its float32 reference.
Chunks of 128 (the default): against 64, at the
cell's shape, `kda_fwd` 10.3 against 14.0 ms a call and `kda_bwd` 25.1
against 26.2, and half the chunk states.  Natively a head must be whole
lane tiles of 128.

`kda(..., kernel=False)` computes the same prologue, chunked algorithm and
epilogue in `jax.numpy`, on views a head (XLA's autodiff, no kernel): the
CPU's path and the path under a mesh.  The process counters
`kda.calls.kernel` and `kda.calls.reference` say which a traced call took.
"""
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import trace
from tensorflowonspark_tpu.ops.ssd import _column, _dot, _dot_t, _row

_F32 = jnp.float32
SUB = 16            # positions of a sub-chunk
_L2_EPS = 1e-6      # of the L2 norms of q and k


def kda(q, k, v, g, beta, w, eps, chunk=128, kernel=False, interpret=None,
        dtype=None):
    """Each head's KDA output under its RMSNorm, `RMSNorm(o) * w`,
    `[batch, S, H * D]` in `dtype` (None: v's type).

    `q`, `k`, `v` [batch, S, H * D] as the convs leave them, a head D lanes
    (q and k not yet normalised: each head's L2 norm, eps 1e-6, is taken
    here, in float32); `g` [batch, S, H * D] float32, the log decay of each
    key channel (<= 0); `beta` [batch, H, S] float32, which scales k and v
    (in float32) before the recurrence; `w` [D] float32 and `eps`, the head
    norm's weight and epsilon, applied to o in float32.  A row is padded to
    whole chunks of `chunk` (zeros: no input, no decay) and cut back after.
    `kernel`: the Pallas kernels, which read and write every operand as it
    lies (`interpret`: None = on the chip natively, elsewhere in the
    interpreter; natively, D must be whole lane tiles of 128), which write
    o and the gradients of q, k and v in `dtype` and hand the gradients on
    in the inputs' types; else the same algorithm in `jax.numpy`, on views
    a head."""
    nb, S, width = q.shape
    H, D = beta.shape[1], w.shape[0]
    dtype = jnp.dtype(dtype or v.dtype)
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not whole sub-chunks of {SUB}")
    pad = -S % chunk
    scale = D ** -0.5
    if kernel:
        trace.counters().inc("kda.calls.kernel")       # once a traced call
        if interpret is None:
            from tensorflowonspark_tpu.ops import default_interpret
            interpret = default_interpret()
        if not interpret and D % 128:
            raise ValueError(
                f"the KDA kernels take a head as whole lane tiles of 128 on "
                f"the chip; kda_head_dim={D} is not")
        if pad:
            q, k, v, g = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                          for t in (q, k, v, g))
            beta = jnp.pad(beta, [(0, 0), (0, 0), (0, pad)])
        o = _kda(q, k, v, g, beta[:, :, None, :], w[None, :], chunk, scale,
                 eps, bool(interpret), dtype)
    else:
        trace.counters().inc("kda.calls.reference")

        def heads(t):
            t = t.reshape(nb, S, H, D).astype(_F32)
            return jnp.pad(t, [(0, 0), (0, pad), (0, 0), (0, 0)])

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                     + _L2_EPS)

        b = jnp.pad(beta.transpose(0, 2, 1), [(0, 0), (0, pad), (0, 0)])
        b = b[..., None]
        q, k, v = unit(heads(q)), unit(heads(k)), heads(v)
        n = (S + pad) // chunk
        G = jnp.cumsum(heads(g).reshape(nb, n, chunk, H, D), axis=2).reshape(
            nb, -1, H, D)
        o = _kda_jnp(q, k, b * k, b * v, G, chunk, scale)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = (o * w).reshape(nb, -1, width).astype(dtype)
    return o[:, :S]


# ---- the chunked algorithm in jax.numpy ------------------------------------


def _products_jnp(xs, y, G, c):
    """`[sum_c x_tc y_sc exp(G_tc - G_sc) for t >= s]` for each x, over
    chunks `[..., L, D]`: by sub-chunks of c as the module's docstring
    says."""
    *lead, L, D = y.shape
    m = L // c
    blocks = G.reshape(*lead, m, c, D)
    first = blocks[..., :1, :]                                  # [.., m, 1, D]
    before = jnp.arange(L)[None, :, None] < (jnp.arange(m) * c)[:, None, None]
    yr = y[..., None, :, :] * jnp.exp(jnp.where(
        before, first - G[..., None, :, :], -jnp.inf))          # [.., m, L, D]
    causal = jnp.tril(jnp.ones((c, c), bool))[:, :, None]
    e = jnp.exp(jnp.where(causal, blocks[..., :, None, :]
                          - blocks[..., None, :, :], -jnp.inf))  # [..m,t,s,D]
    yb = y.reshape(*lead, m, c, D)
    eye = jnp.eye(m, dtype=_F32)
    out = []
    for x in xs:
        xb = x.reshape(*lead, m, c, D)
        off = jnp.einsum("...itd,...isd->...its",
                         xb * jnp.exp(blocks - first), yr)
        diag = jnp.einsum("...itd,...isd,...itsd->...its", xb, yb, e)
        diag = jnp.einsum("...its,ij->...itjs", diag, eye)
        out.append(off.reshape(*lead, L, L) + diag.reshape(*lead, L, L))
    return out


def _kda_jnp(q, k, kb, vb, G, L, scale):
    """The chunked algorithm in `jax.numpy`: `[b, S, H, D]` operands, S
    whole chunks of L; T applied by a triangular solve; o in float32."""
    nb, S, H, D = q.shape
    n = S // L

    def chunks(t):                                     # -> [n, b, H, L, D]
        return t.reshape(nb, n, L, H, D).transpose(1, 0, 3, 2, 4).astype(_F32)

    q, k, kb, vb, G = (chunks(t) for t in (q, k, kb, vb, G))
    aqk, akk = _products_jnp([q, kb], k, G, min(SUB, L))
    aqk = jnp.tril(aqk)
    akk = jnp.tril(akk, -1) + jnp.eye(L, dtype=_F32)
    eg = jnp.exp(G)
    wu = jax.scipy.linalg.solve_triangular(
        akk, jnp.concatenate([kb * eg, vb], axis=-1), lower=True,
        unit_diagonal=True)
    w, big_u = wu[..., :D], wu[..., D:]
    last = G[..., -1:, :]                              # [n, b, H, 1, D]
    kh = k * jnp.exp(last - G)

    def step(s, t):
        w_, u_, qe, a, kh_, keep = t
        u = u_ - w_ @ s
        o = scale * (qe @ s + a @ u)
        return jnp.swapaxes(keep, -1, -2) * s + jnp.swapaxes(kh_, -1, -2) @ u, o

    _, o = jax.lax.scan(step, jnp.zeros((nb, H, D, D), _F32),
                        (w, big_u, q * eg, aqk, kh, jnp.exp(last)))
    return o.transpose(1, 0, 3, 2, 4).reshape(nb, S, H, D)


# ---- the kernels -----------------------------------------------------------


def _dot_tl(a, b):
    """`a^T @ b`."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running(x, back=False):
    """The inclusive running sum down the rows of `[C, D]` (`back`: up
    them, from the last row), in log2(C) shifted adds."""
    C, D = x.shape
    k = 1
    while k < C:
        zeros = jnp.zeros((k, D), _F32)
        x = x + (jnp.concatenate([x[k:], zeros], axis=0) if back
                 else jnp.concatenate([zeros, x[:C - k]], axis=0))
        k *= 2
    return x


def _at_each(x, j, c):
    """`[C, D]` -> every row replaced by row j of its sub-chunk."""
    C, D = x.shape
    m = C // c
    return jnp.broadcast_to(x.reshape(m, c, D)[:, j:j + 1, :],
                            (m, c, D)).reshape(C, D)


def _through(y, G, i, c):
    """`y_s exp(G_r - G_s)` for the rows s before sub-chunk i (r its first
    row), zero from r on: the earlier factor of a pair across sub-chunks."""
    r = i * c
    return y * jnp.exp(jnp.where(_iota((y.shape[0], 1), 0) < r,
                                 G[r:r + 1, :] - G, -jnp.inf))


def _products(xs, y, G, c):
    """`[sum_c x_tc y_sc exp(G_tc - G_sc) for t >= s, else 0]` `[C, C]`
    for each x of `xs`, one y."""
    C = y.shape[0]
    m = C // c
    lead = jnp.exp(G - _at_each(G, 0, c))      # exp(G_t - G_r), r t's first
    pieces = [[jnp.zeros((c, C), _F32)] for _ in xs]
    for i in range(1, m):
        yr = _through(y, G, i, c)
        rows = slice(i * c, (i + 1) * c)
        for p, x in zip(pieces, xs):
            p.append(_dot_t(x[rows] * lead[rows], yr))
    out = [jnp.concatenate(p, axis=0) for p in pieces]
    within = _iota((C, 1), 0) % c
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    for j in range(c):                          # column j of each sub-chunk
        e = _at_each(y, j, c) * jnp.exp(jnp.where(
            within >= j, G - _at_each(G, j, c), -jnp.inf))
        at = cols == (rows // c) * c + j
        out = [jnp.where(at, jnp.sum(x * e, axis=1, keepdims=True), a)
               for a, x in zip(out, xs)]
    return out


def _apply(ms, y, G, c):
    """`[sum_{s <= t} M_ts y_s o exp(G_t - G_s)]` `[C, D]` for each M of
    `ms` (lower triangular `[C, C]`), one y."""
    C, D = y.shape
    m = C // c
    lead = jnp.exp(G - _at_each(G, 0, c))
    pieces = [[jnp.zeros((c, D), _F32)] for _ in ms]
    for i in range(1, m):
        yr = _through(y, G, i, c)
        rows = slice(i * c, (i + 1) * c)
        for p, a in zip(pieces, ms):
            p.append(lead[rows] * _dot(a[rows], yr))
    out = [jnp.concatenate(p, axis=0) for p in pieces]
    within = _iota((C, 1), 0) % c
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    for j in range(c):
        e = _at_each(y, j, c) * jnp.exp(jnp.where(
            within >= j, G - _at_each(G, j, c), -jnp.inf))
        at = cols == (rows // c) * c + j
        out = [r + jnp.sum(jnp.where(at, a, 0.0), axis=1, keepdims=True) * e
               for r, a in zip(out, ms)]
    return out


def _apply_t(pairs, G, c):
    """`sum over (M, x) of [sum_{t >= s} M_ts x_t o exp(G_t - G_s)]`
    `[C, D]`: what `_apply` gives, transposed."""
    C, D = G.shape
    m = C // c
    lead = jnp.exp(G - _at_each(G, 0, c))
    z = jnp.zeros((C, D), _F32)
    for i in range(1, m):
        rows = slice(i * c, (i + 1) * c)
        acc = sum(_dot_tl(a[rows], x[rows] * lead[rows]) for a, x in pairs)
        z = z + _through(acc, G, i, c)
    within = _iota((C, 1), 0) % c
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    flipped = [(a.T, x) for a, x in pairs]
    for j in range(c):                          # t: row j of s's sub-chunk
        e = jnp.exp(jnp.where(j >= within, _at_each(G, j, c) - G, -jnp.inf))
        at = cols == (rows // c) * c + j
        for at_, x in flipped:
            z = z + jnp.sum(jnp.where(at, at_, 0.0), axis=1,
                            keepdims=True) * _at_each(x, j, c) * e
    return z


def _neumann(a, eye, index):
    """`(I + a)^-1` for `a` with `a^index = 0` (index a power of two):
    `(I - a)(I + a^2)(I + a^4)...`."""
    out, p = eye - a, 2
    while p < index:
        a = _dot(a, a)
        out = _dot(out, eye + a)
        p *= 2
    return out


def _inverse(n, c):
    """`(I + N)^-1` of a strictly lower triangular N `[C, C]`: its diagonal
    blocks of c inverted by `_neumann`, then `(I + Tbd L)^-1 Tbd` with L
    the rest, whose blocks are strictly lower."""
    C = n.shape[0]
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    eye = jnp.where(rows == cols, 1.0, 0.0)
    d = jnp.where(rows // c == cols // c, n, 0.0)
    tbd = _neumann(d, eye, c)
    return _dot(_neumann(_dot(tbd, n - d), eye, C // c), tbd)


def _chunk(q, k, kb, vb, G, S, c):
    """A chunk's forward from its inputs (float32) and its starting state:
    `(Aqk, T, W, U, u)`."""
    C = q.shape[0]
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    aqk, akk = _products([q, kb], k, G, c)
    aqk = jnp.where(rows >= cols, aqk, 0.0)
    t = _inverse(jnp.where(rows > cols, akk, 0.0), c)
    w = _dot(t, kb * jnp.exp(G))
    big_u = _dot(t, vb)
    return aqk, t, w, big_u, big_u - _dot(w, S)


def _inputs(q_ref, k_ref, v_ref, beta_ref):
    """A chunk of a head's operands in float32, as the recurrence takes
    them: `(q, 1/|q|, k, 1/|k|, v, beta)`, q and k L2-normalised a row,
    beta the column `[C, 1]` of its row `[1, C]`."""
    def unit(x):
        r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _L2_EPS)
        return x * r, r

    q, rq = unit(q_ref[0].astype(_F32))
    k, rk = unit(k_ref[0].astype(_F32))
    return q, rq, k, rk, v_ref[0].astype(_F32), _column(beta_ref[0, 0])


def _norm(y, eps):
    """`y` under RMSNorm a row (without its weight), and 1/rms."""
    r = jax.lax.rsqrt(jnp.mean(y * y, axis=1, keepdims=True) + eps)
    return y * r, r


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, o_ref, st_ref,
                s_scr, *, scale, eps):
    """One chunk of one (row, head): its output under the head's norm, the
    state at its start written out, the state carried on in VMEM."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    q, _, k, _, v, beta = _inputs(q_ref, k_ref, v_ref, beta_ref)
    G, S = _running(g_ref[0]), s_scr[...]
    C = q.shape[0]
    aqk, _, _, _, u = _chunk(q, k, beta * k, beta * v, G, S, SUB)
    o = scale * (_dot(q * jnp.exp(G), S) + _dot(aqk, u))
    o_ref[0] = (_norm(o, eps)[0] * w_ref[...]).astype(o_ref.dtype)
    st_ref[0, 0, 0] = S
    last = G[C - 1:C, :]
    s_scr[...] = _column(jnp.exp(last)) * S + _dot_tl(
        k * jnp.exp(last - G), u)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, st_ref, dn_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dnw_ref, ds_scr,
                *, scale, eps):
    """One chunk of one (row, head), the chunks taken last first: the
    chunk's forward again, the gradients of its inputs through the head's
    norm, the recurrence, beta and the L2 norms, the gradient of the state
    at its start carried on, the norm's weight's summed over the chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dnw_ref[...] = jnp.zeros_like(dnw_ref)

    q, rq, k, rk, v, beta = _inputs(q_ref, k_ref, v_ref, beta_ref)
    kb, vb = beta * k, beta * v
    G, S = _running(g_ref[0]), st_ref[0, 0, 0]
    ds1 = ds_scr[...]                       # d(the state passed on)
    C = q.shape[0]
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    aqk, t, w, big_u, u = _chunk(q, k, kb, vb, G, S, SUB)
    eg = jnp.exp(G)
    qe, kbe = q * eg, kb * eg
    # out = n w, n = o r, r = (mean(o^2) + eps)^-1/2;  o = scale (qe S + Aqk u)
    n, r = _norm(scale * (_dot(qe, S) + _dot(aqk, u)), eps)
    dn = dn_ref[0].astype(_F32)
    dnw_ref[0, 0] += jnp.sum(dn * n, axis=0, keepdims=True)
    dn = dn * w_ref[...]
    do = scale * r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
    last = G[C - 1:C, :]
    keep, dec = jnp.exp(last), jnp.exp(last - G)
    kh = k * dec
    # o = qe S + Aqk u;  S' = Diag(keep) S + kh^T u
    dqe = _dot_t(do, S)
    daqk = jnp.where(rows >= cols, _dot_t(do, u), 0.0)
    du = _dot_tl(aqk, do) + _dot(kh, ds1)
    ds0 = _dot_tl(qe, do) + _column(keep) * ds1
    dkh = _dot_t(u, ds1)
    dlast = keep * _row(jnp.sum(S * ds1, axis=1, keepdims=True)) \
        + jnp.sum(dkh * kh, axis=0, keepdims=True)
    # u = U - W S;  U = T vb, W = T kbe;  T = (I + Akk)^-1
    dw = -_dot_t(du, S)
    ds0 = ds0 - _dot_tl(w, du)
    dvb = _dot_tl(t, du)
    dkbe = _dot_tl(t, dw)
    dakk = -jnp.where(rows > cols, _dot_t(dvb, big_u) + _dot_t(dkbe, w), 0.0)
    # the pairs: Aqk = P(q, k), Akk = P(kb, k)
    r_q, r_kb = _apply([daqk, dakk], k, G, SUB)
    z = _apply_t([(daqk, q), (dakk, kb)], G, SUB)
    dg = (dkbe * kbe + dqe * qe - dkh * kh + q * r_q + kb * r_kb - k * z
          + jnp.where(_iota((C, 1), 0) == C - 1, dlast, 0.0))
    ds_scr[...] = ds0
    # kb = beta k, vb = beta v;  q, k = x r, r = (|x|^2 + eps)^-1/2
    dkb = dkbe * eg + r_kb
    dq = dqe * eg + r_q
    dk = dkh * dec + z + beta * dkb
    dq_ref[0] = (rq * (dq - q * jnp.sum(q * dq, axis=1, keepdims=True))
                 ).astype(dq_ref.dtype)
    dk_ref[0] = (rk * (dk - k * jnp.sum(k * dk, axis=1, keepdims=True))
                 ).astype(dk_ref.dtype)
    dv_ref[0] = (beta * dvb).astype(dv_ref.dtype)
    dg_ref[0] = _running(dg, back=True)
    dbeta_ref[0, 0] = _row(jnp.sum(dkb * k + dvb * v, axis=1,
                                   keepdims=True))


def _specs(D, n, L, reverse):
    """Block specs over the grid (row, head, chunk): a chunk of a head's
    lanes of `[B, S, H * D]`, a chunk of a head's row of `[B, H, 1, S]`,
    a chunk's state of `[B, H, chunks, D, D]`, the norm's weight `[1, D]`;
    `reverse` walks the chunks last first."""
    def at(c):
        return n - 1 - c if reverse else c

    return (pl.BlockSpec((1, L, D), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, 0, at(c))),
            pl.BlockSpec((1, 1, 1, D, D),
                         lambda b, h, c: (b, h, at(c), 0, 0)),
            pl.BlockSpec((1, D), lambda b, h, c: (0, 0)))


def _call(kernel, name, grid, D, in_specs, out_specs, out_shape, interpret,
          scale, eps):
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, eps=eps), name=name,
        interpret=interpret, grid=grid, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((D, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


def _grid(q, beta, L):
    """`(grid, D)`: (rows, heads, chunks) and a head's lanes."""
    nb, H, _, S = beta.shape
    return (nb, H, S // L), q.shape[2] // H


# Both implementations are jitted on their own, as the SSD kernels are: a
# model calls them once a layer with the same shapes.
@functools.partial(jax.jit, static_argnames=("L", "scale", "eps",
                                             "interpret", "dtype"))
def _fwd_impl(q, k, v, g, beta, w, L, scale, eps, interpret, dtype):
    """`(out [b, S, H * D], states f32[b, H, S / L, D, D])`."""
    grid, D = _grid(q, beta, L)
    seq, row, state, weight = _specs(D, grid[2], L, reverse=False)
    return _call(_fwd_kernel, "kda_fwd", grid, D,
                 [seq] * 4 + [row, weight], [seq, state],
                 [jax.ShapeDtypeStruct(q.shape, dtype),
                  jax.ShapeDtypeStruct(grid + (D, D), _F32)],
                 interpret, scale, eps)(q, k, v, g, beta, w)


@functools.partial(jax.jit, static_argnames=("L", "scale", "eps",
                                             "interpret", "dtype"))
def _bwd_impl(q, k, v, g, beta, w, states, dout, L, scale, eps, interpret,
              dtype):
    """`(dq, dk, dv, dg, dbeta, dw)` in the operands' shapes and types,
    dq, dk and dv written in `dtype`."""
    grid, D = _grid(q, beta, L)
    seq, row, state, weight = _specs(D, grid[2], L, reverse=True)
    by_head = pl.BlockSpec((1, 1, 1, D), lambda b, h, c: (b, h, 0, 0))
    *grads, dw = _call(
        _bwd_kernel, "kda_bwd", grid, D,
        [seq] * 4 + [row, weight, state, seq], [seq] * 4 + [row, by_head],
        [jax.ShapeDtypeStruct(t.shape, dtype) for t in (q, k, v)]
        + [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (g, beta)]
        + [jax.ShapeDtypeStruct(grid[:2] + (1, D), _F32)],
        interpret, scale, eps)(q, k, v, g, beta, w, states, dout)
    return (*(d.astype(t.dtype) for d, t in zip(grads, (q, k, v, g, beta))),
            jnp.sum(dw, axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _kda(q, k, v, g, beta, w, L, scale, eps, interpret, dtype):
    return _fwd_impl(q, k, v, g, beta, w, L, scale, eps, interpret, dtype)[0]


def _kda_vjp_fwd(q, k, v, g, beta, w, L, scale, eps, interpret, dtype):
    out, states = _fwd_impl(q, k, v, g, beta, w, L, scale, eps, interpret,
                            dtype)
    out = checkpoint_name(out, "kda_out")
    states = checkpoint_name(states, "kda_states")
    return out, (q, k, v, g, beta, w, states)


def _kda_vjp_bwd(L, scale, eps, interpret, dtype, res, dout):
    q, k, v, g, beta, w, states = res
    return _bwd_impl(q, k, v, g, beta, w, states, dout.astype(dtype), L,
                     scale, eps, interpret, dtype)


_kda.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)
