"""Flash attention for training as Pallas TPU kernels: a forward that also
gives the rows' logsumexp, and a backward of a dq kernel and a dk/dv
kernel, tied together by ``jax.custom_vjp``.

Layouts: q (B, H, Sq, Dqk); k (B, KV, Sk, Dqk); v (B, KV, Sk, Dv); out
(B, H, Sq, Dv). H % KV == 0 (grouped / multi-query heads by index map);
Dqk and Dv may differ (latent attention: 192 and 128). Queries are
right-aligned when Sq < Sk; causal and sliding-window masks apply.

Three ``pallas_call``s, named so a profile shows them:
  - ``flash_fwd``: key tiles stream past a resident query tile with an
    online softmax; writes out and the row logsumexp;
  - ``flash_dq``: the same walk, accumulating dq;
  - ``flash_dkv``: query tiles stream past a resident key tile,
    accumulating dk and dv in the transposed orientation (k q^T), so the
    logsumexp and delta rows broadcast along lanes with no relayout.

Each walk is a static schedule of the (query tile, key tile) pairs the
mask shows, handed to the kernel by scalar prefetch: a tile the mask hides
wholly is neither fetched nor computed, and costs no grid step. A tile the
mask shows wholly skips the mask arithmetic. A row of tiles that sees
nothing gets one step that only writes its zeros.

Precision: products take operands in ``mxu_dtype`` (the inputs' own by
default) with float32 accumulation; the running max, denominator, exp,
logsumexp and ``ds = p (dp - delta)`` are float32, and p and ds are cast
to the operand dtype only as MXU operands. Gradients come from float32
accumulators and return in the inputs' dtype.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
# step flags of a schedule
_FIRST, _LAST, _RUN, _FULL = 1, 2, 4, 8


def pick_block(n: int):
    """The tile along a sequence of length n: the largest of 512, 256 and
    128 that divides it, or None where none does."""
    return next((b for b in (512, 256, 128) if n % b == 0), None)


@dataclasses.dataclass(frozen=True)
class _Plan:
    causal: bool
    window: int
    scale: float
    bq: int
    bk: int
    nq: int
    nk: int
    offs: int                  # Sk - Sq: query row i sits at key position i + offs
    mxu_dtype: object
    interpret: bool

    def _shows(self, iq, ik):
        """(any, every) (query, key) pair of tile (iq, ik) shown."""
        q0 = iq * self.bq + self.offs
        q1, k0, k1 = q0 + self.bq - 1, ik * self.bk, ik * self.bk + self.bk - 1
        any_, every = True, True
        if self.causal:
            any_, every = k0 <= q1, k1 <= q0
        if self.window:
            any_ = any_ and q0 - k1 < self.window
            every = every and q1 - k0 < self.window
        return any_, every

    def schedule(self, by_key: bool):
        """The walk's steps as int32 arrays (query tile, key tile, flags):
        row by row of query tiles (``by_key``: of key tiles), each row's
        shown tiles in order; a row that sees none gets one step with no
        ``_RUN``, which only initialises and writes it."""
        outer, inner = (self.nk, self.nq) if by_key else (self.nq, self.nk)
        steps = []
        for i in range(outer):
            row = []
            for j in range(inner):
                iq, ik = (j, i) if by_key else (i, j)
                any_, every = self._shows(iq, ik)
                if any_:
                    row.append([iq, ik, _RUN | (_FULL if every else 0)])
            if not row:
                row = [[0, i, 0] if by_key else [i, 0, 0]]
            row[0][2] |= _FIRST
            row[-1][2] |= _LAST
            steps += row
        return tuple(jnp.array(c, jnp.int32) for c in zip(*steps))

    def mask(self, iq, ik, transposed=False):
        """The tile's (bq, bk) visibility, or (bk, bq) when ``transposed``."""
        shape = (self.bk, self.bq) if transposed else (self.bq, self.bk)
        qd, kd = (1, 0) if transposed else (0, 1)
        qpos = iq * self.bq + self.offs + jax.lax.broadcasted_iota(
            jnp.int32, shape, qd)
        kpos = ik * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, kd)
        ok = jnp.ones(shape, bool)
        if self.causal:
            ok &= kpos <= qpos
        if self.window:
            ok &= qpos - kpos < self.window
        return ok

    def dot(self, a, b, dims):
        prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                else None)
        return jax.lax.dot_general(a, b, dims, precision=prec,
                                   preferred_element_type=jnp.float32)


def _steps(qi_ref, ki_ref, fl_ref, init, body, finish):
    """One grid step of a schedule: ``init`` on a row's first step,
    ``body(iq, ik, masked)`` on a shown tile, ``finish`` on its last."""
    t = pl.program_id(2)
    iq, ik, flags = qi_ref[t], ki_ref[t], fl_ref[t]
    pl.when((flags & _FIRST) != 0)(init)
    shown = flags & (_RUN | _FULL)
    pl.when(shown == (_RUN | _FULL))(lambda: body(iq, ik, False))
    pl.when(shown == _RUN)(lambda: body(iq, ik, True))
    pl.when((flags & _LAST) != 0)(finish)


def _cols(x, n):
    """A lane-replicated (rows, 128) statistic, to broadcast over n lanes."""
    return jnp.tile(x, (1, n // 128)) if n % 128 == 0 else x[:, :1]


def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, plan: _Plan):
    def init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(iq, ik, masked):
        v = v_ref[...]
        s = plan.dot(q_ref[...], k_ref[...], _NT) * plan.scale   # (bq, bk)
        if masked:
            ok = plan.mask(iq, ik)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_sc[...]                                       # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _cols(m_next, plan.bk))
        if masked:
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        acc_sc[...] = (acc_sc[...] * _cols(alpha, acc_sc.shape[1])
                       + plan.dot(p.astype(v.dtype), v, _NN))

    def finish():
        l = l_sc[...]
        l = jnp.where(l == 0.0, 1.0, l)          # a row that sees nothing: 0
        o_ref[...] = (acc_sc[...] / _cols(l, acc_sc.shape[1])).astype(
            o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]            # (1, bq)

    _steps(qi_ref, ki_ref, fl_ref, init, body, finish)


def _dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dq_ref, dq_sc, *, plan: _Plan):
    def init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def body(iq, ik, masked):
        k = k_ref[...]
        s = plan.dot(q_ref[...], k, _NT) * plan.scale            # (bq, bk)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        if masked:
            p = jnp.where(plan.mask(iq, ik), p, 0.0)
        dp = plan.dot(do_ref[...], v_ref[...], _NT)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1)) * plan.scale
        dq_sc[...] += plan.dot(ds.astype(k.dtype), k, _NN)

    def finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)

    _steps(qi_ref, ki_ref, fl_ref, init, body, finish)


def _dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, plan: _Plan):
    def init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(iq, ik, masked):
        q, do = q_ref[...], do_ref[...]
        st = plan.dot(k_ref[...], q, _NT) * plan.scale           # (bk, bq)
        pt = jnp.exp(st - lse_ref[...])
        if masked:
            pt = jnp.where(plan.mask(iq, ik, transposed=True), pt, 0.0)
        dv_sc[...] += plan.dot(pt.astype(do.dtype), do, _NN)
        dpt = plan.dot(v_ref[...], do, _NT)
        dst = pt * (dpt - di_ref[...]) * plan.scale
        dk_sc[...] += plan.dot(dst.astype(q.dtype), q, _NN)

    def finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    _steps(qi_ref, ki_ref, fl_ref, init, body, finish)


def _call(kernel, name, plan, by_key, args, in_blocks, out_blocks, out_shape,
          scratch):
    """``pallas_call`` over grid (B, H, steps of the schedule). A block is
    (rows, width, tile) with tile "q", "k" (KV head by index map),
    "k_out" (a key tile of a per-query-head output), or "row" for a
    (1, bq) row of a (B, H, 1, Sq) array."""
    b, h = args[0].shape[:2]
    rep = h // args[1].shape[1]
    sched = plan.schedule(by_key)

    def spec(rows, width, tile):
        def index(ib, ih, t, qi, ki, fl):
            if tile == "q":
                return ib, ih, qi[t], 0
            if tile == "k":
                return ib, ih // rep, ki[t], 0
            if tile == "k_out":
                return ib, ih, ki[t], 0
            return ib, ih, 0, qi[t]
        return pl.BlockSpec((None, None, rows, width), index)

    kw = ({"interpret": True} if plan.interpret else
          {"compiler_params": pltpu.CompilerParams(
              dimension_semantics=("parallel", "parallel", "arbitrary"))})
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, sched[0].shape[0]),
            in_specs=[spec(*blk) for blk in in_blocks],
            out_specs=[spec(*blk) for blk in out_blocks],
            scratch_shapes=scratch),
        out_shape=out_shape,
        name=name,
        **kw,
    )(*sched, *args)


def _fwd(q, k, v, plan: _Plan, out_dtype):
    b, h, sq, dqk = q.shape
    dv, bq, bk = v.shape[3], plan.bq, plan.bk
    return _call(
        _fwd_kernel, "flash_fwd", plan, False, (q, k, v),
        [(bq, dqk, "q"), (bk, dqk, "k"), (bk, dv, "k")],
        [(bq, dv, "q"), (1, bq, "row")],
        [jax.ShapeDtypeStruct((b, h, sq, dv), out_dtype),
         jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        [pltpu.VMEM((bq, 128), jnp.float32),      # running max m
         pltpu.VMEM((bq, 128), jnp.float32),      # running denominator l
         pltpu.VMEM((bq, dv), jnp.float32)])      # output accumulator


def _dq(q, k, v, do, lse, di, plan: _Plan):
    b, h, sq, dqk = q.shape
    dv, bq, bk = v.shape[3], plan.bq, plan.bk
    (dq,) = _call(
        _dq_kernel, "flash_dq", plan, False, (q, k, v, do, lse, di),
        [(bq, dqk, "q"), (bk, dqk, "k"), (bk, dv, "k"), (bq, dv, "q"),
         (1, bq, "row"), (1, bq, "row")],
        [(bq, dqk, "q")],
        [jax.ShapeDtypeStruct((b, h, sq, dqk), jnp.float32)],
        [pltpu.VMEM((bq, dqk), jnp.float32)])
    return dq


def _dkv(q, k, v, do, lse, di, plan: _Plan):
    """dk, dv per query head, (B, H, Sk, D) float32."""
    b, h, _, dqk = q.shape
    sk, dv, bq, bk = k.shape[2], v.shape[3], plan.bq, plan.bk
    return _call(
        _dkv_kernel, "flash_dkv", plan, True, (q, k, v, do, lse, di),
        [(bq, dqk, "q"), (bk, dqk, "k"), (bk, dv, "k"), (bq, dv, "q"),
         (1, bq, "row"), (1, bq, "row")],
        [(bk, dqk, "k_out"), (bk, dv, "k_out")],
        [jax.ShapeDtypeStruct((b, h, sk, dqk), jnp.float32),
         jax.ShapeDtypeStruct((b, h, sk, dv), jnp.float32)],
        [pltpu.VMEM((bk, dqk), jnp.float32), pltpu.VMEM((bk, dv), jnp.float32)])


def _operands(plan, *xs):
    return [x if plan.mxu_dtype is None else x.astype(plan.mxu_dtype)
            for x in xs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, plan):
    return _flash_fwd(q, k, v, plan)[0]


def _flash_fwd(q, k, v, plan):
    qm, km, vm = _operands(plan, q, k, v)
    out, lse = _fwd(qm, km, vm, plan, q.dtype)
    return out, (qm, km, vm, out, lse)


def _flash_bwd(plan, res, dout):
    qm, km, vm, out, lse = res
    b, h, sq, _ = qm.shape
    kvh, sk = km.shape[1], km.shape[2]
    di = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)[:, :, None, :]                         # (B,H,1,Sq)
    (dom,) = _operands(plan, dout.astype(vm.dtype))
    dq = _dq(qm, km, vm, dom, lse, di, plan)
    dk, dv = _dkv(qm, km, vm, dom, lse, di, plan)
    if h != kvh:                              # sum the heads that share a KV
        dk = dk.reshape(b, kvh, h // kvh, sk, -1).sum(2)
        dv = dv.reshape(b, kvh, h // kvh, sk, -1).sum(2)
    return tuple(g.astype(out.dtype) for g in (dq, dk, dv))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale=None, block_q=None, block_k=None,
                           mxu_dtype=None, interpret: bool = False):
    """Differentiable flash attention; see the module docstring for the
    layouts. Tiles default to ``pick_block`` of each sequence length;
    ``mxu_dtype`` is the products' operand dtype (None: the inputs')."""
    b, h, sq, dqk = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    assert h % kvh == 0, "GQA requires H % KV == 0"
    assert q.dtype == k.dtype == v.dtype, "q, k and v share one dtype"
    bq = min(block_q or pick_block(sq) or sq, sq)
    bk = min(block_k or pick_block(sk) or sk, sk)
    assert sq % bq == 0 and sk % bk == 0, "seq lens must divide block sizes"
    plan = _Plan(causal=causal, window=window,
                 scale=scale if scale is not None else 1.0 / math.sqrt(dqk),
                 bq=bq, bk=bk, nq=sq // bq, nk=sk // bk, offs=sk - sq,
                 mxu_dtype=None if mxu_dtype is None else jnp.dtype(mxu_dtype),
                 interpret=interpret)
    return _flash(q, k, v, plan)
