"""Flash attention Pallas TPU kernels — forward + backward, custom VJP.

TPU adaptation (DESIGN.md §6): the GPU flash algorithm's warp-level softmax
reductions become full-tile VPU reductions; tiles are MXU-aligned
(block_q × head_dim and block_k × head_dim multiples of 128 where the
head_dim allows). GQA is expressed in the K/V BlockSpec index maps
(kv_head = q_head // group), so no K/V replication is materialized in HBM.

The sliding ``window`` and causal flags arrive as scalar-prefetch operands
(SMEM), keeping one compiled kernel for gemma3's per-layer local/global mix.

Grid-level block pruning (index-map-level, the DMA saving)
----------------------------------------------------------
The (q_block, k_block) iteration space is flattened to a 1-D *cell* axis
enumerating only the block pairs that are live under the **statically known**
mask structure (the causal flag is always static; ``window`` too when passed
as a Python int). Three small int32 scalar-prefetch tables — cell→q_block,
cell→k_block, and first/last/dead-row flags — drive every BlockSpec index
map, so a skipped K-block is never DMA'd from HBM at all: the launched grid
shrinks (causal: nq·(nq+1)/2 of nq·nk cells), not just the executed FLOPs.
This is strictly stronger than the PR-1 scheme, which kept the dense grid
and early-exited via ``pl.when`` — saving the tile math but still paying the
HBM→VMEM copies the BlockSpec pipeline had already issued. When the window
is a *traced* scalar (gemma3's scan-over-layers), causal pruning still
shrinks the grid and the traced-window deadness falls back to the ``pl.when``
predicate inside the surviving cells; fully-live interior blocks skip the
iota/compare/select mask arithmetic via ``lax.cond``. ``block_skip=False``
restores the dense grid for ablation. The cell axis is innermost-sequential
("arbitrary"); batch and head stay parallel for megacore partitioning.

Statically-empty rows (e.g. K-rows beyond the causal horizon when t > s in
the dk/dv grid) get one sentinel *dead-row* cell that only zero-initializes
and flushes the output block, so every output tile is written exactly once.

Backward pass (the training hot path)
-------------------------------------
``flash_attention`` is a ``jax.custom_vjp`` built on the shared
``kernels.vjp`` harness: gradients never differentiate the
interpreter/Mosaic forward. The forward additionally emits the per-row
logsumexp ``lse = m + log(l)`` (fp32, stored lane-broadcast as
(B,H,S,LANES) so its blocks meet Mosaic's tiling rule) so the backward
recomputes probabilities directly as ``P = exp(S·scale − lse)`` without
re-running the online softmax. Two passes share the grid machinery:

* **dq pass** — q-major pruned cells. The Δ = rowsum(dO ∘ O) preprocess is
  fused into the first cell of each q-row (an fp32 VMEM scratch reduction
  over the already-resident dO/O tiles — no separate XLA pass over
  (B,H,S,D)) and emitted as a (B,H,S,LANES) by-product for the dk/dv
  pass. Per
  K-cell: ``dP = dO·Vᵀ``, ``dS = P ∘ (dP − Δ)``, ``dq += scale · dS·K``
  into an fp32 VMEM accumulator flushed at the last cell of the row.
* **dk/dv pass** — k-major pruned cells over (k_block, group, q_block) with
  (group, q_block) innermost-sequential, so dK/dV accumulate over every
  query head of the GQA group and every live Q-block in fp32 VMEM scratch
  and are written once per K-block — the GQA reduction stays in the
  BlockSpec index maps, no (B,H,T,D) per-q-head gradient is ever
  materialized in HBM.

Ragged tails (``s % block_q`` or ``t % block_k`` ≠ 0): out-of-bounds block
reads are undefined (NaN in interpret mode), so the tile masks include
bounds terms, probabilities are formed with NaN-discarding ``where``, and
tiles that feed a matmul against an exactly-zero factor (V in forward; Q,
dO, O, K, V in backward) are zeroed beyond the sequence edge — 0·NaN would
otherwise poison the accumulators. Fully-masked rows write
``lse = +LSE_BIG`` so the backward's ``exp(S − lse)`` underflows to 0.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vjp

NEG_INF = -2.0 ** 30
LSE_BIG = 2.0 ** 30     # lse stand-in for fully-masked rows: exp(s-LSE_BIG)=0

# cell-table flag bits
_FIRST = 1              # first cell of its output row: init accumulators
_LAST = 2               # last cell: flush accumulators to the output block
_DEAD = 4               # sentinel for a statically-empty row: zero-fill only


class _Spec(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff arg).

    ``causal``/``window`` mirror the traced meta operands for grid pruning:
    ``window=None`` means the runtime value is traced (pruning then uses the
    causal structure only and defers window deadness to the in-kernel
    predicate)."""
    block_q: int
    block_k: int
    interpret: bool
    block_skip: bool
    causal: bool
    window: Optional[int]


# ---------------------------------------------------------------------------
# block-level predicates (traced: causal/window live in SMEM)
# ---------------------------------------------------------------------------

def _block_dead(causal, window, qi, ki, block_q, block_k):
    """True iff K-block ki is entirely masked for Q-block qi."""
    q_min = qi * block_q
    q_max = q_min + block_q - 1
    k_min = ki * block_k
    k_max = k_min + block_k - 1
    dead_causal = (causal > 0) & (k_min > q_max)
    dead_window = (window > 0) & ((q_min - k_max) >= window)
    return dead_causal | dead_window


def _block_needs_mask(causal, window, qi, ki, block_q, block_k, s, t):
    """False iff every (q,k) pair in the tile is live and in-bounds."""
    q_min = qi * block_q
    q_max = q_min + block_q - 1
    k_min = ki * block_k
    k_max = k_min + block_k - 1
    cut_causal = (causal > 0) & (k_max > q_min)
    cut_window = (window > 0) & ((q_max - k_min) >= window)
    ragged = (q_max >= s) | (k_max >= t)
    return cut_causal | cut_window | ragged


def _tile_mask(causal, window, qi, ki, block_q, block_k, s, t):
    """(block_q, block_k) bool mask: causal ∧ window ∧ bounds."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    # scalar-gated terms as `~flag | term`: Mosaic cannot select between
    # bool vectors, so no jnp.where on masks
    mask = (q_pos < s) & (k_pos < t)
    mask &= (causal <= 0) | (k_pos <= q_pos)
    mask &= (window <= 0) | ((q_pos - k_pos) < window)
    return mask


_row_valid = vjp.row_valid     # shared ragged-tail row mask (harness)


def _guard_compute(compute, flag, causal, window, qi, ki, block_q, block_k,
                   *, block_skip, static_window):
    """Run the tile body under the cheapest correct predicate: dense grids
    run unguarded; statically-pruned grids (window known at trace time)
    only need the dead-row sentinel check — every launched non-sentinel
    cell is live by construction of the host cell tables; traced-window
    grids re-check deadness from the SMEM scalars."""
    if not block_skip:
        compute()
    elif static_window:
        pl.when((flag & _DEAD) == 0)(compute)
    else:
        pl.when(jnp.logical_not(
            _block_dead(causal, window, qi, ki, block_q, block_k)
            | ((flag & _DEAD) != 0)))(compute)


# ---------------------------------------------------------------------------
# static cell enumeration (host ints -> SMEM prefetch tables)
# ---------------------------------------------------------------------------

def _host_dead(spec, qi, ki):
    """Host-int mirror of _block_dead under the *statically known* flags."""
    q_min = qi * spec.block_q
    q_max = q_min + spec.block_q - 1
    k_min = ki * spec.block_k
    k_max = k_min + spec.block_k - 1
    dead = spec.causal and (k_min > q_max)
    if spec.window is not None and spec.window > 0:
        dead = dead or (q_min - k_max) >= spec.window
    return dead


def _cells_q_major(spec, nq, nk):
    """(cq, ck, cflag) int32 tables for the fwd/dq grids: q-row-major live
    cells, one dead-row sentinel per statically-empty q-row."""
    cq, ck, cf = [], [], []
    for qi in range(nq):
        live = [ki for ki in range(nk)
                if not (spec.block_skip and _host_dead(spec, qi, ki))]
        if not live:
            cq.append(qi)
            ck.append(0)
            cf.append(_FIRST | _LAST | _DEAD)
            continue
        for j, ki in enumerate(live):
            cq.append(qi)
            ck.append(ki)
            cf.append((_FIRST if j == 0 else 0)
                      | (_LAST if j == len(live) - 1 else 0))
    return (np.asarray(cq, np.int32), np.asarray(ck, np.int32),
            np.asarray(cf, np.int32))


def _cells_k_major(spec, nq, nk, group):
    """(ck, cg, cq, cflag) tables for the dk/dv grid: k-row-major over
    (k_block, group, q_block); accumulators span a whole k-row."""
    ck, cg, cq, cf = [], [], [], []
    for ki in range(nk):
        live = [qi for qi in range(nq)
                if not (spec.block_skip and _host_dead(spec, qi, ki))]
        if not live:
            ck.append(ki)
            cg.append(0)
            cq.append(0)
            cf.append(_FIRST | _LAST | _DEAD)
            continue
        for gi in range(group):
            for j, qi in enumerate(live):
                ck.append(ki)
                cg.append(gi)
                cq.append(qi)
                cf.append(
                    (_FIRST if gi == 0 and j == 0 else 0)
                    | (_LAST if gi == group - 1 and j == len(live) - 1
                       else 0))
    return (np.asarray(ck, np.int32), np.asarray(cg, np.int32),
            np.asarray(cq, np.int32), np.asarray(cf, np.int32))


def grid_cells(s, t, *, causal, window=0, block_q=128, block_k=128,
               block_skip=True):
    """(launched, dense) q-major cell counts — the benchmark's DMA-pruning
    ablation reads the *actual* grid size the kernel launches."""
    spec = _Spec(min(block_q, s), min(block_k, t), True, block_skip,
                 bool(causal), int(window))
    nq = pl.cdiv(s, spec.block_q)
    nk = pl.cdiv(t, spec.block_k)
    return len(_cells_q_major(spec, nq, nk)[0]), nq * nk


# ---------------------------------------------------------------------------
# forward kernel (online softmax, emits lse residual)
# ---------------------------------------------------------------------------

def _fwd_kernel(meta_ref, cq_ref, ck_ref, cf_ref,  # SMEM scalar prefetch
                q_ref, k_ref, v_ref,  # VMEM tiles
                o_ref, lse_ref,       # VMEM out tiles
                m_scr, l_scr, acc_scr,
                *, block_q, block_k, scale, seq_q, seq_k, block_skip,
                static_window):
    c = pl.program_id(2)
    qi = cq_ref[c]
    ki = ck_ref[c]
    flag = cf_ref[c]
    causal = meta_ref[0]
    window = meta_ref[1]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        # zero OOB V rows: P columns there are exactly 0 and 0*NaN = NaN
        v = jnp.where(_row_valid(ki, block_k, seq_k),
                      v_ref[0, 0].astype(jnp.float32), 0.0)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jax.lax.cond(
            _block_needs_mask(causal, window, qi, ki, block_q, block_k,
                              seq_q, seq_k),
            lambda x: jnp.where(_tile_mask(causal, window, qi, ki, block_q,
                                           block_k, seq_q, seq_k),
                                x, NEG_INF),
            lambda x: x, s)

        m_prev = m_scr[...]                        # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (bq, bk)

        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _guard_compute(_compute, flag, causal, window, qi, ki, block_q, block_k,
                   block_skip=block_skip, static_window=static_window)

    @pl.when((flag & _LAST) != 0)
    def _finish():
        m = m_scr[...]
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        # fully-masked rows (m never updated) get LSE_BIG so that the
        # backward's exp(s - lse) underflows to an exact 0
        lse = jnp.where(m > 0.5 * NEG_INF, m + jnp.log(l), LSE_BIG)
        lse_ref[0, 0] = vjp.to_lanes(lse)


def _forward(spec, meta, q, k, v):
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    dv = v.shape[3]
    g = h // kh
    bq = min(spec.block_q, s)
    bk = min(spec.block_k, t)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(t, bk)
    rspec = spec._replace(block_q=bq, block_k=bk)
    cq, ck, cf = (jnp.asarray(x) for x in _cells_q_major(rspec, nq, nk))

    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, scale=d ** -0.5,
        seq_q=s, seq_k=t, block_skip=spec.block_skip,
        static_window=spec.window is not None)

    # index maps receive (*grid_indices, *scalar_prefetch_refs)
    def q_map(bb, hh, c, meta, cq, ck, cf):
        return (bb, hh, cq[c], 0)

    def kv_map(bb, hh, c, meta, cq, ck, cf):
        return (bb, hh // g, ck[c], 0)

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, cq.shape[0]),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), q_map),
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, dv), q_map),
                pl.BlockSpec((1, 1, bq, vjp.LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, vjp.LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
    )(meta, cq, ck, cf, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels: recompute P from lse, fp32 accumulators
# ---------------------------------------------------------------------------

def _load_bwd_tiles(q_ref, k_ref, v_ref, do_ref, lse_ref,
                    qi, ki, block_q, block_k, seq_q, seq_k):
    """Shared dq/dkv tile prologue: fp32 upcast with OOB rows zeroed (OOB
    block reads are undefined — NaN in interpret mode — and every tile here
    feeds a matmul whose other factor is exactly 0 in that region)."""
    kv_ok = _row_valid(ki, block_k, seq_k)
    q_ok = _row_valid(qi, block_q, seq_q)
    q = jnp.where(q_ok, q_ref[0, 0].astype(jnp.float32), 0.0)
    k = jnp.where(kv_ok, k_ref[0, 0].astype(jnp.float32), 0.0)
    v = jnp.where(kv_ok, v_ref[0, 0].astype(jnp.float32), 0.0)
    do = jnp.where(q_ok, do_ref[0, 0].astype(jnp.float32), 0.0)
    lse = vjp.from_lanes(lse_ref[0, 0])            # (bq, 1)
    return q, k, v, do, lse


def _recompute_p_ds(causal, window, qi, ki, block_q, block_k, seq_q, seq_k,
                    scale, s_, dp, lse, delta):
    """P = exp(S − lse); dS = scale · P ∘ (dP − Δ). Fully-live blocks skip
    the mask arithmetic (lax.cond); masked entries go through where() so
    NaN/inf from OOB reads never propagate."""
    def _with_mask(_):
        mask = _tile_mask(causal, window, qi, ki, block_q, block_k,
                          seq_q, seq_k)
        p = jnp.where(mask, jnp.exp(s_ - lse), 0.0)
        ds = jnp.where(mask, p * (dp - delta), 0.0) * scale
        return p, ds

    def _no_mask(_):
        p = jnp.exp(s_ - lse)
        return p, p * (dp - delta) * scale

    return jax.lax.cond(
        _block_needs_mask(causal, window, qi, ki, block_q, block_k,
                          seq_q, seq_k),
        _with_mask, _no_mask, None)


def _dq_kernel(meta_ref, cq_ref, ck_ref, cf_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
               dq_ref, delta_ref, dq_scr, delta_scr,
               *, block_q, block_k, scale, seq_q, seq_k, block_skip,
               static_window):
    c = pl.program_id(2)
    qi = cq_ref[c]
    ki = ck_ref[c]
    flag = cf_ref[c]
    causal = meta_ref[0]
    window = meta_ref[1]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # fused Δ = rowsum(dO ∘ O): the O/dO tiles are resident for this
        # q-row anyway, so the old standalone XLA pass over (B,H,S,D) folds
        # into one fp32 VPU reduction at the first cell of the row
        q_ok = _row_valid(qi, block_q, seq_q)
        o = jnp.where(q_ok, o_ref[0, 0].astype(jnp.float32), 0.0)
        do = jnp.where(q_ok, do_ref[0, 0].astype(jnp.float32), 0.0)
        delta_scr[...] = jnp.sum(o * do, axis=-1, keepdims=True)
        delta_ref[0, 0] = vjp.to_lanes(delta_scr[...])

    def _compute():
        q, k, v, do, lse = _load_bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref,
            qi, ki, block_q, block_k, seq_q, seq_k)
        delta = delta_scr[...]
        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        _, ds = _recompute_p_ds(causal, window, qi, ki, block_q, block_k,
                                seq_q, seq_k, scale, s_, dp, lse, delta)
        dq_scr[...] += jax.lax.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    _guard_compute(_compute, flag, causal, window, qi, ki, block_q, block_k,
                   block_skip=block_skip, static_window=static_window)

    @pl.when((flag & _LAST) != 0)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(meta_ref, ck_ref, cg_ref, cq_ref, cf_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, block_q, block_k, scale, seq_q, seq_k, block_skip,
                static_window):
    c = pl.program_id(2)
    ki = ck_ref[c]
    qi = cq_ref[c]
    flag = cf_ref[c]
    causal = meta_ref[0]
    window = meta_ref[1]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q, k, v, do, lse = _load_bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref,
            qi, ki, block_q, block_k, seq_q, seq_k)
        delta = vjp.from_lanes(delta_ref[0, 0])    # (bq, 1)
        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        p, ds = _recompute_p_ds(causal, window, qi, ki, block_q, block_k,
                                seq_q, seq_k, scale, s_, dp, lse, delta)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),       # pᵀ · dO  (bk, dv)
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),       # dsᵀ · Q  (bk, d)
            preferred_element_type=jnp.float32)

    _guard_compute(_compute, flag, causal, window, qi, ki, block_q, block_k,
                   block_skip=block_skip, static_window=static_window)

    @pl.when((flag & _LAST) != 0)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _backward_dq(spec, meta, q, k, v, do, out, lse):
    """dq pass over the q-major pruned cells; emits the fused Δ by-product
    the dk/dv pass consumes."""
    b, h, s, d = q.shape
    t = k.shape[2]
    dv_dim = v.shape[3]
    g = h // k.shape[1]
    bq = min(spec.block_q, s)
    bk = min(spec.block_k, t)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(t, bk)
    rspec = spec._replace(block_q=bq, block_k=bk)
    cq, ck, cf = (jnp.asarray(x) for x in _cells_q_major(rspec, nq, nk))

    def q_map(bb, hh, c, meta, cq, ck, cf):
        return (bb, hh, cq[c], 0)

    def kv_map(bb, hh, c, meta, cq, ck, cf):
        return (bb, hh // g, ck[c], 0)

    dq_kernel = functools.partial(
        _dq_kernel, block_q=bq, block_k=bk, scale=d ** -0.5,
        seq_q=s, seq_k=t, block_skip=spec.block_skip,
        static_window=spec.window is not None)

    dq, delta = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, cq.shape[0]),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), q_map),
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, dv_dim), kv_map),
                pl.BlockSpec((1, 1, bq, dv_dim), q_map),
                pl.BlockSpec((1, 1, bq, vjp.LANES), q_map),
                pl.BlockSpec((1, 1, bq, dv_dim), q_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), q_map),
                pl.BlockSpec((1, 1, bq, vjp.LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, vjp.LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
    )(meta, cq, ck, cf, q, k, v, do, lse, out)
    return dq, delta


def _backward_dkv(spec, meta, q, k, v, do, lse, delta):
    """dk/dv pass: k-major pruned cells over (k_block, group, q_block); the
    fp32 scratch accumulates the whole GQA group before one flush per
    K-block."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    dv_dim = v.shape[3]
    g = h // kh
    bq = min(spec.block_q, s)
    bk = min(spec.block_k, t)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(t, bk)
    rspec = spec._replace(block_q=bq, block_k=bk)
    ck2, cg2, cq2, cf2 = (jnp.asarray(x)
                          for x in _cells_k_major(rspec, nq, nk, g))

    def q_map2(bb, kk, c, meta, ck, cg, cq, cf):
        return (bb, kk * g + cg[c], cq[c], 0)

    def kv_map2(bb, kk, c, meta, ck, cg, cq, cf):
        return (bb, kk, ck[c], 0)

    dkv_kernel = functools.partial(
        _dkv_kernel, block_q=bq, block_k=bk, scale=d ** -0.5,
        seq_q=s, seq_k=t, block_skip=spec.block_skip,
        static_window=spec.window is not None)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, kh, ck2.shape[0]),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), q_map2),
                pl.BlockSpec((1, 1, bk, d), kv_map2),
                pl.BlockSpec((1, 1, bk, dv_dim), kv_map2),
                pl.BlockSpec((1, 1, bq, dv_dim), q_map2),
                pl.BlockSpec((1, 1, bq, vjp.LANES), q_map2),
                pl.BlockSpec((1, 1, bq, vjp.LANES), q_map2),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d), kv_map2),
                pl.BlockSpec((1, 1, bk, dv_dim), kv_map2),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dv_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, kh, t, dv_dim), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
    )(meta, ck2, cg2, cq2, cf2, q, k, v, do, lse, delta)
    return dk, dv


# ---------------------------------------------------------------------------
# custom VJP plumbing (shared kernels.vjp harness)
# ---------------------------------------------------------------------------

def _flash_fwd(spec, meta, q, k, v):
    out, lse = _forward(spec, meta, q, k, v)
    return out, (meta, q, k, v, out, lse)


def _flash_bwd(spec, res, do):
    meta, q, k, v, out, lse = res
    dq, delta = _backward_dq(spec, meta, q, k, v, do, out, lse)
    dk, dv = _backward_dkv(spec, meta, q, k, v, do, lse, delta)
    return vjp.float0_like(meta), dq, dk, dv


_flash = vjp.differentiable(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _meta(causal, window):
    return jnp.array([1 if causal else 0, 0], jnp.int32) \
        .at[1].set(jnp.asarray(window, jnp.int32))


def _make_spec(causal, window, block_q, block_k, interpret, block_skip):
    # window participates in static grid pruning only when it is a host int;
    # a traced window prunes on the causal structure and falls back to the
    # in-kernel predicate for window deadness
    wstat = int(window) if isinstance(window, (int, np.integer)) else None
    return _Spec(int(block_q), int(block_k), bool(interpret),
                 bool(block_skip), bool(causal), wstat)


@functools.partial(jax.jit, static_argnums=(0,))
def _flash_call(spec, meta, q, k, v):
    return _flash(spec, meta, q, k, v)


@functools.partial(jax.jit, static_argnums=(0,))
def _forward_call(spec, meta, q, k, v):
    out, lse = _forward(spec, meta, q, k, v)
    return out, lse[..., 0]


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False, block_skip=True):
    """q (B,H,S,D), k/v (B,KH,T,D). window: int (static -> grid pruning) or
    traced int32 scalar (0=full). Differentiable (custom VJP, Pallas
    backward kernels). Returns (B,H,S,D) in q.dtype."""
    spec = _make_spec(causal, window, block_q, block_k, interpret, block_skip)
    return _flash_call(spec, _meta(causal, window), q, k, v)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, block_q=128,
                        block_k=128, interpret=False, block_skip=True):
    """Forward returning ``(out, lse)`` — the fp32 (B,H,S) logsumexp
    residual the backward consumes (exposed for tests/inspection)."""
    spec = _make_spec(causal, window, block_q, block_k, interpret, block_skip)
    return _forward_call(spec, _meta(causal, window), q, k, v)
