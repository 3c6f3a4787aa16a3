"""Batched-JAX fast path for the RAN MAC: ``lax.scan`` over TTIs, arrays
over the flow axis.

``core/ran.py`` stays the bitwise ORACLE: every number this module
produces -- grants, HARQ outcomes, finish timestamps, PF EWMA state --
must equal the Python engine exactly, so the PR-5 golden-trace harness
keeps pinning one semantics for both engines.  The speed comes from
shape, not approximation:

  * One ``lax.scan`` step per TTI instead of a Python loop iteration.
    The per-TTI scheduler state (byte queues, HARQ ledgers, PRB grants,
    EWMA rates, finish times) rides in the scan carry as float64/int64
    arrays over the flow axis.
  * RR / PF / EDF grant logic is closed-form vectorized: PF and EDF are
    a stable ``jnp.lexsort`` plus a masked cumulative-sum greedy fill
    (the exact closed form of ``_greedy_fill``); RR finds its water
    level by integer bisection on ``sum(min(need, L)) <= n_prbs`` and
    hands the remainder out by rotated rank (the closed form of
    ``_equal_fill``).
  * HARQ uniforms are PRE-DRAWN from the caller's numpy Generator into
    a flat tape and consumed inside the scan through a moving pointer.
    Drawing ``rng.random(K)`` yields the same value stream as K
    successive ``rng.random(n_i)`` calls, so pre-drawing keeps the
    draw-for-draw pairing with the oracle; values the kernel did not
    consume stay on the tape for the next call (the tape owns the tail
    of the stream, the Generator the rest).

Exactness discipline (why the odd-looking bits exist):

  * Everything runs in float64 under ``jax.enable_x64(True)`` --
    scoped, so the f32 model/kernel stack in the same process is
    untouched.
  * XLA:CPU contracts ``a*b + c`` into an FMA, which rounds once where
    numpy rounds twice.  ``_seal`` pipes a product through a bitcast +
    xor with a RUNTIME zero (a constant zero would be folded away),
    which no backend can contract through; every product that feeds an
    add goes through it.
  * Sorting uses ``jnp.lexsort`` / stable ``argsort`` only -- verified
    permutation-identical to ``np.lexsort`` including tie stability.
  * Float ``cumsum`` is forbidden in kernel code (XLA's prefix scan
    associates differently); the only cumulative sums here are int64.

The scan kernel is resumable: a step that cannot execute (drained, past
``until_s``, tape exhausted, TTI guard) latches a stop code into the
carry and the remaining steps no-op; the host driver inspects the code,
refills the tape or raises, and re-enters.  That makes one compiled
kernel serve both ``serve_slot`` (drain one frame-slot) and the
continuous ``RanStream`` clock (bounded ``advance``).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.ran import (DeadlineEDFScheduler, GrantReport,
                            ProportionalFairScheduler, RanCell, RanConfig,
                            RoundRobinScheduler, SchedulerPolicy, StreamFlow,
                            UplinkRequest, MCS_SE, RE_PER_PRB)

# policy codes (static argument of the compiled kernels)
_RR, _PF, _EDF = 0, 1, 2
_POLICY_CODE = {RoundRobinScheduler: _RR, ProportionalFairScheduler: _PF,
                DeadlineEDFScheduler: _EDF}
_PF_ALPHA = ProportionalFairScheduler.alpha
_PF_EPS = ProportionalFairScheduler.eps_bps

# driver stop codes latched by the scan
_RUNNING, _DONE, _TIME_UP, _TAPE_OUT, _SLOT_GUARD = 0, 1, 2, 3, 4

# tape chunk budget: at most this many pre-drawn uniforms in flight
_MAX_BUF = 1 << 22


def policy_code(policy: SchedulerPolicy) -> int:
    """Static kernel code for an oracle policy instance; rejects
    subclasses (their overridden ``grant`` could not be replicated)."""
    code = _POLICY_CODE.get(type(policy))
    if code is None:
        raise ValueError(
            f"engine='vectorized' supports exactly the stock rr/pf/edf "
            f"schedulers; got {type(policy).__name__} (run the Python "
            f"engine for custom policies)")
    return code


def _pad_len(n: int, floor: int = 8) -> int:
    """Next power of two (compile-cache bucketing for growing axes)."""
    p = floor
    while p < n:
        p <<= 1
    return p


def mcs_index_vec(bits_per_prb: np.ndarray) -> np.ndarray:
    """Vector form of ``ran.mcs_index``: last MCS with SE <= payload."""
    se = np.asarray(bits_per_prb, float) / RE_PER_PRB
    return np.maximum(
        np.searchsorted(np.asarray(MCS_SE), se, side="right") - 1, 0)


# ---------------------------------------------------------------------------
# kernel building blocks (traced under enable_x64; f64/i64 throughout)
# ---------------------------------------------------------------------------

def _seal(v, z):
    """Round-trip a float64 product through int64 bits xor a RUNTIME
    zero: no backend can contract the following add into an FMA, and no
    simplifier can cancel the xor (z's value is only known at run time).
    Bitwise identity on the value itself."""
    import jax.numpy as jnp
    from jax import lax
    return lax.bitcast_convert_type(
        lax.bitcast_convert_type(v, jnp.int64) ^ z, jnp.float64)


def _greedy_alloc(order, need, n_prbs):
    """Closed form of ``ran._greedy_fill`` on a full permutation: each
    request sees the grid minus everything granted before it."""
    import jax.numpy as jnp
    no = need[order]
    cum = jnp.cumsum(no)
    fill = jnp.clip(n_prbs - (cum - no), 0, no)
    return jnp.zeros_like(need).at[order].set(fill)


def _grant_kernel(policy: int, n_prbs: int, active, need, dead, ue, bpp,
                  tti, rr_ptr, pf_avg, z):
    """One TTI's PRB allocation -- the vectorized twin of
    ``policy.grant(view)``.  Inactive rows carry zero need and +inf sort
    keys, so their presence never changes an active row's grant."""
    import jax.numpy as jnp
    from jax import lax
    n = need.shape[0]
    inf = jnp.float64(jnp.inf)
    if policy == _EDF:
        order = jnp.lexsort((ue, need, jnp.where(active, dead, inf)))
        return _greedy_alloc(order, need, n_prbs)
    if policy == _PF:
        inst = bpp * n_prbs / tti
        metric = inst / jnp.maximum(pf_avg[ue], _PF_EPS)
        order = jnp.lexsort((ue, jnp.where(active, -metric, inf)))
        return _greedy_alloc(order, need, n_prbs)
    # RR: water level by integer bisection, remainder by rotated rank
    n_act = jnp.sum(active.astype(jnp.int64))
    safe = jnp.maximum(n_act, 1)
    arank = jnp.cumsum(active.astype(jnp.int64)) - 1
    start = rr_ptr % safe
    rot = jnp.where(active, (arank - start) % safe, n)

    def bisect(_, lh):
        lo, hi = lh
        mid = (lo + hi + 1) // 2
        ok = jnp.sum(jnp.minimum(need, mid)) <= n_prbs
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    iters = max(int(n_prbs).bit_length() + 1, 1)
    level, _ = lax.fori_loop(0, iters, bisect,
                             (jnp.int64(0), jnp.int64(n_prbs)))
    got = jnp.minimum(need, level)
    left = n_prbs - jnp.sum(got)
    unsat = need > level
    by_rot = jnp.argsort(rot, stable=True)
    u_sorted = unsat[by_rot]
    bonus_sorted = u_sorted & (jnp.cumsum(u_sorted.astype(jnp.int64)) - 1
                               < left)
    bonus = jnp.zeros(n, bool).at[by_rot].set(bonus_sorted)
    return got + bonus.astype(jnp.int64)


def _grant_fast(policy: int, n_prbs: int, active, rem, dead, ue, bpp,
                tti, rr_ptr, pf_avg, z):
    """``_grant_kernel`` with the full-lane comparator sort replaced by
    cheap primitives -- bitwise-identical allocations.

    XLA:CPU's f64 sort costs ~1 ms per 4k lanes; its f32 ``top_k`` custom
    call costs ~50 us per 16k.  So: RR needs no sort at all (the rotated
    rank is a permutation, so the bonus ranks collapse to two cumsums);
    EDF/PF select top-K candidates by a MONOTONE f32 downcast of the
    priority key, then order just those K rows by the exact f64 composite
    key.  The downcast is weakly monotone (no inversions, only
    collisions), so the candidate set provably covers the granted prefix
    whenever (a) every boundary tie fit inside K and (b) the grid is
    exhausted within the candidates (or all actives fit).  When either
    check fails -- adversarial tie pileups, huge grids -- a ``lax.cond``
    falls back to the exact full-lane sort, so the fast path is an
    optimization, never a semantic.

    Returns ``(alloc, gdx)``: the per-lane PRB grant plus the (distinct)
    indices of every granted lane, KD rows (each grant is >= 1 PRB, so
    at most n_prbs lanes are granted; rows past the granted count point
    at alloc-0 lanes).  In the candidate fast path the granted set is
    selected among the K candidate rows, so the extra top_k runs over
    256 lanes, not the full F."""
    import jax.numpy as jnp
    from jax import lax
    n = rem.shape[0]
    KD = min(n, _pad_len(n_prbs + 1, 128))
    inf = jnp.float64(jnp.inf)

    def granted_of(alloc):
        return lax.top_k((alloc > 0).astype(jnp.float32),
                         KD)[1].astype(jnp.int64)

    if policy == _RR:
        need = _need_prbs(active, rem, bpp)
        n_act = jnp.sum(active.astype(jnp.int64))
        start = rr_ptr % jnp.maximum(n_act, 1)
        # rank arithmetic never exceeds the lane count and the bisection
        # sum is capped at n*(n_prbs+1), so run both in i32 when that
        # fits: XLA:CPU i64 cumsum/reduce lanes cost ~2x i32
        sdt = jnp.int32 if n * (n_prbs + 1) < 2**31 else jnp.int64
        need_s = jnp.minimum(need, n_prbs + 1).astype(sdt)
        arank = jnp.cumsum(active.astype(sdt)) - 1

        def bisect(_, lh):
            lo, hi = lh
            mid = (lo + hi + 1) // 2
            ok = jnp.sum(jnp.minimum(need_s, mid)) <= n_prbs
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

        iters = max(int(n_prbs).bit_length() + 1, 1)
        level, _ = lax.fori_loop(0, iters, bisect,
                                 (sdt(0), sdt(n_prbs)))
        got = jnp.minimum(need, level.astype(jnp.int64))
        left = (n_prbs - jnp.sum(got)).astype(sdt)
        unsat = need_s > level
        # rot order = unsat with arank >= start (ascending), then the
        # wrapped block arank < start; ranks via two cumsums
        in_a = unsat & (arank >= start.astype(sdt))
        in_b = unsat & ~(arank >= start.astype(sdt))
        cs_a = jnp.cumsum(in_a.astype(sdt))
        cs_b = jnp.cumsum(in_b.astype(sdt))
        cnt_less = jnp.where(in_a, cs_a - 1, cs_a[n - 1] + cs_b - 1)
        bonus = unsat & (cnt_less < left)
        alloc = got + bonus.astype(jnp.int64)
        return alloc, granted_of(alloc)

    K = min(n, max(256, _pad_len(n_prbs + 1)))
    if policy == _PF:
        metric = (bpp * n_prbs / tti) / jnp.maximum(pf_avg[ue], _PF_EPS)
        key32 = jnp.where(active, metric, -inf).astype(jnp.float32)
    else:
        metric = None
        key32 = jnp.where(active, -dead, -inf).astype(jnp.float32)
    vals, cidx = lax.top_k(key32, K)
    # min over the (descending-sorted) top-K == the K-th value, but
    # consuming the WHOLE vals slice keeps XLA's TopK custom-call
    # rewrite alive: slicing ``vals[K-1]`` alone collapses to a
    # slice-of-sort that the TopkRewriter no longer pattern-matches,
    # silently reverting to a ~30x slower full comparator sort
    vk = jnp.min(vals)
    cnt_ge = jnp.sum((key32 >= vk) & active)
    n_act = jnp.sum(active.astype(jnp.int64))
    # ceil(rem/bpp) is lane-local, so computing it on the K candidate
    # rows is bitwise the same as gathering from the full-lane version
    act_c = active[cidx]
    need_c = _need_prbs(act_c, rem[cidx], bpp[cidx])
    ue_c = ue[cidx]
    safe = (cnt_ge <= K) & ((n_act <= K)
                            | (jnp.sum(need_c) >= n_prbs))

    def fast(_):
        if policy == _PF:
            order = jnp.lexsort((ue_c, jnp.where(act_c, -metric[cidx],
                                                 inf)))
        else:
            order = jnp.lexsort((ue_c, need_c,
                                 jnp.where(act_c, dead[cidx], inf)))
        no = need_c[order]
        cum = jnp.cumsum(no)
        fill = jnp.clip(n_prbs - (cum - no), 0, no)
        alloc_c = jnp.zeros_like(need_c).at[order].set(fill)
        kdx = lax.top_k((alloc_c > 0).astype(jnp.float32), KD)[1]
        return (jnp.zeros(n, jnp.int64).at[cidx].set(alloc_c),
                cidx[kdx].astype(jnp.int64))

    def slow(_):
        alloc = _grant_kernel(policy, n_prbs, active,
                              _need_prbs(active, rem, bpp), dead, ue,
                              bpp, tti, rr_ptr, pf_avg, z)
        return alloc, granted_of(alloc)

    return lax.cond(safe, fast, slow, None)


def _need_prbs(active, rem, bpp):
    """Twin of ``SlotView.need_prbs``."""
    import jax.numpy as jnp
    return jnp.where(active, jnp.ceil(rem / bpp), 0.0).astype(jnp.int64)


def _pf_observe(pf_avg, active, delivered, ue, tti, z):
    """Twin of ``ProportionalFairScheduler.observe`` (active UEs are
    unique per TTI, so scatter-add into zeros equals the oracle's
    fancy-index assignment)."""
    import jax.numpy as jnp
    served = jnp.zeros_like(pf_avg).at[ue].add(
        jnp.where(active, delivered / tti, 0.0))
    return (_seal((1.0 - _PF_ALPHA) * pf_avg, z)
            + _seal(_PF_ALPHA * served, z))


def _pf_observe_sparse(pf_avg, gidx, gvalid, ue, delivered_g, tti, z):
    """``_pf_observe`` scattering only the granted lanes (``gidx``,
    validity mask ``gvalid``, pre-gathered deliveries).  Active-but-
    unserved lanes contribute exactly +0.0 in the dense version and the
    accumulator never goes negative (so no -0.0), hence dropping them
    is bitwise free."""
    import jax.numpy as jnp
    served = jnp.zeros_like(pf_avg).at[ue[gidx]].add(
        jnp.where(gvalid, delivered_g / tti, 0.0))
    return (_seal((1.0 - _PF_ALPHA) * pf_avg, z)
            + _seal(_PF_ALPHA * served, z))


# ---------------------------------------------------------------------------
# compiled chunk kernels
# ---------------------------------------------------------------------------

def _slot_chunk_impl(carry, enq, dead, bpp, ue, buf, n_draw, tti, bler,
                     max_slots, *, steps: int, n_prbs: int, policy: int,
                     record: bool):
    """Up to ``steps`` scan iterations of ``RanCell.serve_slot``'s TTI
    loop.  ``n_draw`` uniforms consumed per EXECUTED TTI from ``buf``
    (= the cell's REAL request count: padded lanes read garbage past the
    pointer but are inactive, so the rng stream stays paired with the
    oracle); idle-gap jumps consume neither a draw nor a TTI.  Un-jitted
    so ``core/engine_vec.py`` can vmap it over a cell axis; the jitted
    single-cell wrapper is ``_slot_chunk`` below."""
    import jax.numpy as jnp
    from jax import lax
    n = enq.shape[0]

    def step(c, _):
        (code, k, ptr, rr_ptr, z, rem, fin, grt, act, ntx, nrx, pfa) = c
        now = _seal(k.astype(jnp.float64) * tti, z)
        undrained = rem > 0.0
        done_all = ~jnp.any(undrained)
        hit_max = k >= max_slots
        active = (enq <= now) & undrained
        any_act = jnp.any(active)
        running = code == _RUNNING
        new_code = jnp.where(~running, code,
                    jnp.where(done_all, _DONE,
                     jnp.where(hit_max, _SLOT_GUARD, _RUNNING)))
        exec_t = running & ~done_all & ~hit_max & any_act
        idle_t = running & ~done_all & ~hit_max & ~any_act

        need = _need_prbs(active, rem, bpp)
        alloc = _grant_kernel(policy, n_prbs, active, need, dead, ue, bpp,
                              tti, rr_ptr, pfa, z)
        sent = jnp.minimum(rem, alloc * bpp)
        u = lax.dynamic_slice(buf, (ptr,), (n,))
        fail = (u < bler) & (alloc > 0)
        delivered = jnp.where(fail, 0.0, sent)
        rem2 = rem - delivered
        newly = (rem2 <= 1e-9) & jnp.isnan(fin)
        fin2 = jnp.where(newly, now + tti, fin)
        rem3 = jnp.where(rem2 <= 1e-9, 0.0, rem2)
        pfa2 = _pf_observe(pfa, active, delivered, ue, tti, z) \
            if policy == _PF else pfa

        pend_min = jnp.min(jnp.where(undrained, enq, jnp.inf))
        k_idle = jnp.ceil(pend_min / tti).astype(jnp.int64)

        w = lambda a, b: jnp.where(exec_t, a, b)
        c2 = (new_code,
              jnp.where(exec_t, k + 1, jnp.where(idle_t, k_idle, k)),
              w(ptr + n_draw, ptr), w(rr_ptr + 1, rr_ptr) if policy == _RR
              else rr_ptr, z,
              w(rem3, rem), w(fin2, fin), w(grt + alloc, grt),
              w(act + active.astype(jnp.int64), act),
              w(ntx + (alloc > 0).astype(jnp.int64), ntx),
              w(nrx + fail.astype(jnp.int64), nrx), pfa2 if policy != _PF
              else w(pfa2, pfa))
        ys = (k, alloc, delivered, fail, exec_t) if record else None
        return c2, ys

    return lax.scan(step, carry, None, length=steps)


_slot_chunk = partial(__import__("jax").jit, static_argnames=(
    "steps", "n_prbs", "policy", "record"))(_slot_chunk_impl)


@partial(__import__("jax").jit,
         static_argnames=("steps", "n_prbs", "policy"))
def _stream_chunk(carry, enq, dead, bpp, ue, seg, seg_size, nxt_flow,
                  enq_sorted, fail_bits, valid_len, tti, max_slots, until,
                  *, steps: int, n_prbs: int, policy: int):
    """Up to ``steps`` scan iterations of ``RanStream.advance``'s TTI
    loop over ALL tracked flows (padded rows point at an empty cohort
    segment, so they neither draw nor transmit).  Per executed TTI one
    uniform per flow of every unretired cohort, in admission order.

    Per-TTI derived state is maintained INCREMENTALLY in the carry so an
    executed TTI costs a handful of O(F) elementwise masks + O(K)
    scatters, never a full sort, full-lane scatter, or (in the common
    case) even a full-lane reduction:

      * ``is_hol[F+1]``: a UE's earliest-admitted undrained flow claims
        the queue (even before its enqueue instant).  Only HOL flows are
        granted, so at most one flow per UE drains per TTI, and its
        successor is the STATIC next-same-UE index ``nxt_flow`` -- two
        K-row scatters.  Slot F is the sentinel target for chain tails.
      * ``open_cnt[n_seg]``: the oracle's ``_cohort_open`` counter per
        cohort segment (entry value = host dict).  At most n_prbs flows
        drain per executed TTI (draining needs a delivery), so the
        decrements are a K-row scatter; cohort retirement shifts the
        draw list at exactly the oracle's TTI.  The per-TTI draw count
        is the segment-size sum over open segments, and the draw list is
        a contiguous prefix while every real segment stays open.
      * ``n_live`` / ``n_drained`` scalars: drained flows were granted,
        hence eligible, hence ``enq <= now`` -- so the eligible count is
        ``searchsorted(enq_sorted, now) - n_drained`` and the next
        arrival is ``enq_sorted[cnt]``, both O(log F).

    The HARQ tape arrives as PRE-COMPARED fail bits (``u < bler`` done
    host-side -- the stream path never needs the uniform's value, and
    1-byte lanes cost 8x less to transfer than f64).  Stopped steps
    short-circuit through ``lax.cond``."""
    import jax.numpy as jnp
    from jax import lax
    F = enq.shape[0]
    KD = min(F, _pad_len(n_prbs + 1, 128))

    def run_step(c):
        (code, k, ptr, nstep, rr_ptr, z, rem, fin, grt, act, ntx, nrx,
         pfa, is_hol, open_cnt, n_live, n_drained) = c
        now = _seal(k.astype(jnp.float64) * tti, z)
        live_any = n_live > 0
        time_up = now >= until - 1e-12
        cnt_enq = jnp.searchsorted(enq_sorted, now,
                                   side="right").astype(jnp.int64)
        any_elig = cnt_enq - n_drained > 0
        hit_max = nstep >= max_slots
        seg_open = open_cnt > 0
        nd = jnp.sum(jnp.where(seg_open, seg_size, 0))
        can_draw = ptr + nd <= valid_len

        def code_of(nxt_k):
            jump_stop = nxt_k.astype(jnp.float64) * tti >= until - 1e-12
            return jnp.where(~live_any, _DONE,
                    jnp.where(time_up, _TIME_UP,
                     jnp.where(~any_elig & jump_stop, _TIME_UP,
                      jnp.where(any_elig & hit_max, _SLOT_GUARD,
                       jnp.where(any_elig & ~can_draw, _TAPE_OUT,
                                 _RUNNING)))))

        exec_t = live_any & ~time_up & any_elig & ~hit_max & can_draw

        def do_exec(c):
            (code, k, ptr, nstep, rr_ptr, z, rem, fin, grt, act, ntx,
             nrx, pfa, is_hol, open_cnt, n_live, n_drained) = c
            active = (rem > 0.0) & (enq <= now) & is_hol[:F]
            # every grant is >= 1 PRB, so at most n_prbs lanes (gdx)
            # change state this TTI; the whole HARQ / drain / counter
            # update below is O(KD), not O(F)
            alloc, gdx = _grant_fast(policy, n_prbs, active, rem, dead,
                                     ue, bpp, tti, rr_ptr, pfa, z)
            alloc_g = alloc[gdx]
            gvalid = alloc_g > 0
            # real flows sit in lanes [0, n): while every real segment
            # is open the drawn lanes are exactly that prefix and a
            # lane's draw rank is its own index
            contig = jnp.all(seg_open | (seg_size == 0))
            rank_g = lax.cond(
                contig,
                lambda _: gdx,
                lambda _: jnp.cumsum(
                    (open_cnt[seg] > 0).astype(jnp.int64))[gdx] - 1,
                None)
            u_fail = fail_bits[jnp.clip(ptr + rank_g, 0,
                                        fail_bits.shape[0] - 1)]
            rem_g = rem[gdx]
            sent_g = jnp.minimum(rem_g, alloc_g * bpp[gdx])
            fail_g = u_fail & gvalid
            delivered_g = jnp.where(fail_g, 0.0, sent_g)
            rem2_g = rem_g - delivered_g
            # unserved live lanes always keep rem > 1e-9 (the oracle
            # zeroes on drain), so drains happen only on granted lanes
            newly_g = gvalid & (rem2_g <= 1e-9)
            ndrain = jnp.sum(newly_g.astype(jnp.int64))
            fin2 = fin.at[gdx].set(jnp.where(newly_g, now + tti,
                                             fin[gdx]))
            rem3 = rem.at[gdx].set(jnp.where(newly_g, 0.0, rem2_g))
            open2 = open_cnt.at[seg[gdx]].add(-newly_g.astype(jnp.int64))
            hol2 = is_hol.at[gdx].set(is_hol[gdx] & ~newly_g)
            tgt = jnp.where(newly_g, nxt_flow[gdx], F)
            hol3 = hol2.at[tgt].set(hol2[tgt] | newly_g)
            if policy == _PF:
                pfa2 = _pf_observe_sparse(pfa, gdx, gvalid, ue,
                                          delivered_g, tti, z)
            else:
                pfa2 = pfa
            rr2 = jnp.where(jnp.any(active), rr_ptr + 1, rr_ptr) \
                if policy == _RR else rr_ptr
            return (code_of(jnp.int64(0)), k + 1, ptr + nd, nstep + 1,
                    rr2, z, rem3, fin2,
                    grt.at[gdx].add(jnp.where(gvalid, alloc_g, 0)),
                    act + active.astype(jnp.int64),
                    ntx.at[gdx].add(gvalid.astype(jnp.int64)),
                    nrx.at[gdx].add(fail_g.astype(jnp.int64)), pfa2,
                    hol3, open2, n_live - ndrain, n_drained + ndrain)

        def do_rest(c):
            # pending flows all have enq > now (drained ones were
            # eligible), so the earliest pending arrival is the next
            # entry of the sorted (inf-padded) arrival list
            pend_min = enq_sorted[jnp.clip(cnt_enq, 0,
                                           enq_sorted.shape[0] - 1)]
            nxt_k = jnp.ceil(pend_min / tti).astype(jnp.int64)
            jump_stop = nxt_k.astype(jnp.float64) * tti >= until - 1e-12
            idle_t = live_any & ~time_up & ~any_elig & ~jump_stop
            k2 = jnp.where(idle_t, jnp.maximum(c[1], nxt_k), c[1])
            return (code_of(nxt_k), k2) + c[2:]

        return lax.cond(exec_t, do_exec, do_rest, c)

    def step(c, _):
        return lax.cond(c[0] == _RUNNING, run_step, lambda x: x, c), None

    return lax.scan(step, carry, None, length=steps)[0]


# ---------------------------------------------------------------------------
# host-side driver state
# ---------------------------------------------------------------------------

class _UniformTape:
    """The tail of a numpy Generator's uniform stream, pre-drawn.  The
    kernel consumes values through a pointer; anything drawn but not
    consumed stays here, so across calls the (tape + generator) pair
    yields exactly the oracle's draw sequence."""

    def __init__(self):
        self.buf = np.empty(0, np.float64)

    def fill(self, rng: np.random.Generator, want: int):
        if self.buf.size < want:
            self.buf = np.concatenate(
                [self.buf, rng.random(want - self.buf.size)])

    def consume(self, count: int):
        self.buf = self.buf[count:]


def _chunk_schedule(n_lanes: int):
    """Scan lengths per chunk: start small (tiny slots should not pay a
    4k-step scan), grow geometrically, respect the tape budget."""
    cap = max(_MAX_BUF // max(n_lanes, 1), 16)
    steps = 64
    while True:
        yield min(steps, cap)
        steps = min(steps * 4, 4096)


def _x64():
    import jax
    return jax.enable_x64(True)


def require_f64_bitcast_backend():
    """Refuse a TPU backend up front.  ``_seal`` bitcasts f64 <-> s64, and
    the TPU compiler's X64 rewriter refuses that op ("UNIMPLEMENTED ...
    bitcast-convert"), so no scan kernel here compiles for the chip.
    Failing at construction keeps a run from quietly moving the MAC to
    another device; ``engine='python'`` runs everywhere."""
    import jax
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            "the vectorized MAC engine cannot run on a TPU backend: its "
            "f64 kernels bitcast f64<->s64 (ran_vec._seal), which the TPU "
            "compiler refuses (UNIMPLEMENTED bitcast-convert in its X64 "
            "rewrite); use engine='python'")


@dataclass
class VecRanCell:
    """Drop-in ``RanCell`` twin running the scan kernel.  Construct via
    ``VecRanCell.from_cell(cell)``; policy state (PF EWMA, RR pointer)
    lives here as numpy arrays and persists across slots exactly like
    the oracle policy object's."""
    policy: int
    cfg: RanConfig = field(default_factory=RanConfig)
    record_trace: bool = False
    grant_trace: List[Tuple[int, Tuple]] = field(default_factory=list)

    def __post_init__(self):
        require_f64_bitcast_backend()
        self._rr_ptr = 0
        self._pf_avg = np.zeros(0)
        self._tape = _UniformTape()

    @classmethod
    def from_cell(cls, cell: RanCell) -> "VecRanCell":
        vc = cls(policy=policy_code(cell.policy), cfg=cell.cfg,
                 record_trace=cell.record_trace)
        # adopt live policy state so mid-run conversion stays paired
        if isinstance(cell.policy, ProportionalFairScheduler):
            avg = cell.policy._avg
            vc._pf_avg = np.array(avg, float)
        elif isinstance(cell.policy, RoundRobinScheduler):
            vc._rr_ptr = int(cell.policy._ptr)
        return vc

    def reset(self, n_ues: int):
        self._rr_ptr = 0
        self._pf_avg = np.zeros(n_ues if self.policy == _PF else 0)
        self._tape = _UniformTape()
        self.grant_trace = []

    def bits_per_prb(self, link_rate_bps):
        return (np.asarray(link_rate_bps, float) * self.cfg.tti_s
                / (self.cfg.n_prbs * (1.0 - self.cfg.bler_target)))

    def _ensure_pf(self, max_ue: int):
        want = _pad_len(max_ue + 1)
        if self._pf_avg.size < want:
            old = self._pf_avg
            self._pf_avg = np.zeros(want)
            self._pf_avg[:old.size] = old

    # -- one frame-slot ------------------------------------------------------
    def serve_slot_arrays(self, ue, n_bytes, enq, dead, link_rate_bps,
                          harq_rng: np.random.Generator) -> Dict[str, np.ndarray]:
        """Array-in / array-out ``serve_slot``: the report fields as
        vectors (identical floats to the oracle's ``GrantReport``s)."""
        import jax.numpy as jnp
        cfg = self.cfg
        self.grant_trace = []
        n = len(ue)
        out: Dict[str, np.ndarray] = {}
        if n == 0:
            return out
        ue = np.asarray(ue, int)
        n_bytes = np.asarray(n_bytes, int)
        enq = np.asarray(enq, float)
        dead = np.asarray(dead, float)
        rem = n_bytes * 8.0
        bpp = self.bits_per_prb(np.asarray(link_rate_bps, float))
        finish = np.where(rem > 0, np.nan, enq)
        k0 = int(math.ceil(enq.min() / cfg.tti_s))
        if self.policy == _PF:
            self._ensure_pf(int(ue.max()))

        with _x64():
            carry = (jnp.int64(_RUNNING), jnp.int64(k0), jnp.int64(0),
                     jnp.int64(self._rr_ptr), jnp.int64(0),
                     jnp.asarray(rem), jnp.asarray(finish),
                     jnp.zeros(n, jnp.int64), jnp.zeros(n, jnp.int64),
                     jnp.zeros(n, jnp.int64), jnp.zeros(n, jnp.int64),
                     jnp.asarray(self._pf_avg))
            jenq, jdead, jbpp, jue = (jnp.asarray(enq), jnp.asarray(dead),
                                      jnp.asarray(bpp), jnp.asarray(ue))
            for steps in _chunk_schedule(n):
                self._tape.fill(harq_rng, steps * n)
                buf = jnp.asarray(self._tape.buf[:steps * n])
                carry, ys = _slot_chunk(
                    carry, jenq, jdead, jbpp, jue, buf, jnp.int64(n),
                    jnp.float64(cfg.tti_s), jnp.float64(cfg.bler_target),
                    jnp.int64(cfg.max_slots), steps=steps,
                    n_prbs=cfg.n_prbs, policy=self.policy,
                    record=self.record_trace)
                code = int(carry[0])
                self._tape.consume(int(carry[2]))
                carry = carry[:2] + (jnp.int64(0),) + carry[3:]
                if self.record_trace:
                    self._append_trace(ys, ue)
                if code == _DONE:
                    break
                if code == _SLOT_GUARD:
                    raise RuntimeError(
                        f"RanCell: uplink queues not drained after "
                        f"{cfg.max_slots} TTIs "
                        f"({cfg.max_slots * cfg.tti_s:.1f} s simulated); "
                        f"raise RanConfig.max_slots or reduce the "
                        f"offered load")
            self._rr_ptr = int(carry[3])
            if self.policy == _PF:
                self._pf_avg = np.asarray(carry[11])
            finish = np.asarray(carry[6])
            granted = np.asarray(carry[7])
            act = np.asarray(carry[8])
            out = dict(finish_s=finish, granted_prbs=granted,
                       active_slots=act, n_tx=np.asarray(carry[9]),
                       n_harq_retx=np.asarray(carry[10]))
        tx_s = finish - enq
        out["tx_s"] = tx_s
        out["realized_rate_bps"] = np.where(tx_s > 0, n_bytes * 8.0
                                            / np.where(tx_s > 0, tx_s, 1.0),
                                            0.0)
        out["prb_share"] = np.where(act > 0, granted
                                    / np.where(act > 0, cfg.n_prbs * act, 1),
                                    0.0)
        out["mcs"] = mcs_index_vec(bpp)
        out["bpp"] = bpp
        return out

    def _append_trace(self, ys, ue):
        ks, alloc, delivered, fail, execd = (np.asarray(y) for y in ys)
        for t in np.flatnonzero(execd):
            g = np.flatnonzero(alloc[t])
            self.grant_trace.append((int(ks[t]), tuple(
                (int(ue[i]), int(alloc[t, i]), int(delivered[t, i]),
                 bool(fail[t, i])) for i in g)))

    def serve_slot(self, requests: Sequence[UplinkRequest],
                   harq_rng: np.random.Generator) -> Dict[int, GrantReport]:
        """Oracle-identical ``RanCell.serve_slot`` (object API)."""
        self.grant_trace = []
        if not requests:
            return {}
        ue = np.array([r.ue_id for r in requests])
        nb = np.array([r.n_bytes for r in requests])
        a = self.serve_slot_arrays(
            ue, nb, np.array([r.enqueue_s for r in requests]),
            np.array([r.deadline_s for r in requests]),
            np.array([r.link_rate_bps for r in requests]), harq_rng)
        reports = {}
        for i, r in enumerate(requests):
            reports[int(ue[i])] = GrantReport(
                ue_id=int(ue[i]), n_bytes=int(nb[i]),
                enqueue_s=float(r.enqueue_s), finish_s=float(a["finish_s"][i]),
                tx_s=float(a["tx_s"][i]), granted_prbs=int(a["granted_prbs"][i]),
                active_slots=int(a["active_slots"][i]),
                n_tx=int(a["n_tx"][i]), n_harq_retx=int(a["n_harq_retx"][i]),
                realized_rate_bps=float(a["realized_rate_bps"][i]),
                prb_share=float(a["prb_share"][i]), mcs=int(a["mcs"][i]))
        return reports


# ---------------------------------------------------------------------------
# continuous-TTI streaming twin
# ---------------------------------------------------------------------------

_PARK_COLS = ("ue", "bpp", "coh", "rem", "grt", "act", "ntx", "nrx", "gaa")


class ParkedFlows:
    """Blackout-parked flows in ARRAY form (the parked lane, DESIGN.md
    §11): the rows ``migrate_ues`` pops from a ``VecRanStream`` kept as
    column arrays plus the carried request/meta object lists, so a mass
    park/adopt cycle stays a handful of numpy ops instead of per-flow
    ``StreamFlow`` shuffling.  Columns carry exactly what ``adopt_batch``
    re-admits -- remaining bits and the accumulated grant/HARQ counters
    (enqueue/deadline/rate re-derive from the carried request) -- plus
    the popped cohort and spectral efficiency so ``flows()`` can
    materialize oracle-identical ``StreamFlow`` views for parity tests."""

    __slots__ = _PARK_COLS + ("reqs", "meta")

    def __init__(self, ue=None, bpp=None, coh=None, rem=None, grt=None,
                 act=None, ntx=None, nrx=None, gaa=None, reqs=None,
                 meta=None):
        zi, zf = np.zeros(0, np.int64), np.zeros(0, np.float64)
        self.ue = zi if ue is None else ue
        self.bpp = zf if bpp is None else bpp
        self.coh = zi if coh is None else coh
        self.rem = zf if rem is None else rem
        self.grt = zi if grt is None else grt
        self.act = zi if act is None else act
        self.ntx = zi if ntx is None else ntx
        self.nrx = zi if nrx is None else nrx
        self.gaa = zi if gaa is None else gaa
        self.reqs = [] if reqs is None else reqs
        self.meta = [] if meta is None else meta

    def __len__(self) -> int:
        return int(self.ue.size)

    def take(self, idx: np.ndarray) -> "ParkedFlows":
        """Row subset (order-preserving fancy index)."""
        return ParkedFlows(
            **{c: getattr(self, c)[idx] for c in _PARK_COLS},
            reqs=[self.reqs[i] for i in idx],
            meta=[self.meta[i] for i in idx])

    @classmethod
    def concat(cls, batches: Sequence["ParkedFlows"]) -> "ParkedFlows":
        batches = [b for b in batches if len(b)]
        if not batches:
            return cls()
        return cls(
            **{c: np.concatenate([getattr(b, c) for b in batches])
               for c in _PARK_COLS},
            reqs=[r for b in batches for r in b.reqs],
            meta=[m for b in batches for m in b.meta])

    def flush_tb(self):
        """Charge every in-flight HARQ transport block as a loss (the
        park-time rule: the adopting cell cannot soft-combine another
        cell's HARQ process) -- one vectorized compare."""
        self.nrx = self.nrx + (self.grt > self.gaa)

    def flows(self) -> List[StreamFlow]:
        """Materialize ``StreamFlow`` views (tests / python interop);
        the hot path never calls this."""
        return [StreamFlow(
            req=self.reqs[i], cohort=int(self.coh[i]), meta=self.meta[i],
            rem_bits=float(self.rem[i]), bpp=float(self.bpp[i]),
            granted=int(self.grt[i]), act_slots=int(self.act[i]),
            n_tx=int(self.ntx[i]), n_retx=int(self.nrx[i]),
            finish_s=float("nan"), granted_at_admit=int(self.gaa[i]))
            for i in range(len(self))]


class VecRanStream:
    """Drop-in ``RanStream`` twin: flow state as growing numpy arrays in
    admission order, TTIs executed by ``_stream_chunk``.  Finished /
    migrated flows materialize as real ``StreamFlow`` objects, so
    ``timeline.run_stream`` needs no special cases."""

    def __init__(self, cell: RanCell, n_ues: int = 0):
        self.cell = VecRanCell.from_cell(cell) \
            if isinstance(cell, RanCell) else cell
        self.cfg = self.cell.cfg
        self._k = 0
        self._n = 0                      # live array length
        self._cap = 16
        # the oracle's cohort -> open-flow counter, mirrored exactly:
        # +1 per enqueue/adopt, -1 when a flow drains in advance or
        # migrates out, key deleted at zero (= cohort retirement)
        self._cohort_open: Dict[int, int] = {}
        self._meta: List[object] = []
        self._reqs: List[UplinkRequest] = []
        f, i = np.float64, np.int64
        self._ue = np.zeros(self._cap, i)
        self._enq = np.zeros(self._cap, f)
        self._dead = np.zeros(self._cap, f)
        self._bpp = np.zeros(self._cap, f)
        self._rem = np.zeros(self._cap, f)
        self._fin = np.zeros(self._cap, f)
        self._grt = np.zeros(self._cap, i)
        self._act = np.zeros(self._cap, i)
        self._ntx = np.zeros(self._cap, i)
        self._nrx = np.zeros(self._cap, i)
        self._gaa = np.zeros(self._cap, i)   # granted_at_admit
        self._coh = np.zeros(self._cap, i)
        if n_ues and self.cell.policy == _PF and not self.cell._pf_avg.size:
            self.cell._pf_avg = np.zeros(n_ues)

    def _grow(self):
        self._cap *= 2
        for name in ("_ue", "_enq", "_dead", "_bpp", "_rem", "_fin",
                     "_grt", "_act", "_ntx", "_nrx", "_gaa", "_coh"):
            old = getattr(self, name)
            arr = np.zeros(self._cap, old.dtype)
            arr[:self._n] = old[:self._n]
            setattr(self, name, arr)

    def _append(self, req: UplinkRequest, cohort: int, meta, rem_bits,
                granted=0, act_slots=0, n_tx=0, n_retx=0,
                granted_at_admit=0) -> int:
        if self._n == self._cap:
            self._grow()
        i = self._n
        self._n += 1
        self._ue[i] = req.ue_id
        self._enq[i] = req.enqueue_s
        self._dead[i] = req.deadline_s
        self._bpp[i] = float(self.cell.bits_per_prb(req.link_rate_bps))
        self._rem[i] = rem_bits
        self._fin[i] = np.nan
        self._grt[i] = granted
        self._act[i] = act_slots
        self._ntx[i] = n_tx
        self._nrx[i] = n_retx
        self._gaa[i] = granted_at_admit
        self._coh[i] = cohort
        self._meta.append(meta)
        self._reqs.append(req)
        return i

    def enqueue(self, req: UplinkRequest, cohort: int,
                meta: object = None) -> StreamFlow:
        i = self._append(req, cohort, meta, req.n_bytes * 8.0)
        self._cohort_open[cohort] = self._cohort_open.get(cohort, 0) + 1
        return self._flow_view(i)

    def _flow_view(self, i: int) -> StreamFlow:
        return StreamFlow(
            req=self._reqs[i], cohort=int(self._coh[i]), meta=self._meta[i],
            rem_bits=float(self._rem[i]), bpp=float(self._bpp[i]),
            granted=int(self._grt[i]), act_slots=int(self._act[i]),
            n_tx=int(self._ntx[i]), n_retx=int(self._nrx[i]),
            finish_s=float(self._fin[i]) if self._rem[i] <= 0.0
            else float("nan"), granted_at_admit=int(self._gaa[i]))

    # -- the TTI clock -------------------------------------------------------
    def advance(self, until_s: float,
                harq_rng: np.random.Generator) -> List[StreamFlow]:
        import jax.numpy as jnp
        cfg = self.cfg
        n = self._n
        if n == 0:
            return []
        was_live = self._rem[:n] > 0.0
        if not was_live.any():
            return []
        # compact cohort ids -> segment indices (+1 reserved empty pad)
        coh_ids, seg = np.unique(self._coh[:n], return_inverse=True)
        n_seg = _pad_len(coh_ids.size + 1)
        base_open = np.zeros(n_seg, np.int64)
        base_open[:coh_ids.size] = [self._cohort_open.get(int(c), 0)
                                    for c in coh_ids]
        F = _pad_len(n)
        if self.cell.policy == _PF:
            self.cell._ensure_pf(int(self._ue[:n].max()))
        pfa = self.cell._pf_avg
        ue_pad = _pad_len(max(int(self._ue[:n].max()) + 1, pfa.size, 1))

        def pad(a, fill=0):
            out = np.full(F, fill, a.dtype)
            out[:n] = a[:n]
            return out

        ue = pad(self._ue)
        seg_p = np.full(F, n_seg - 1, np.int64)
        seg_p[:n] = seg
        # static HOL chain over ENTRY-undrained flows: per UE, admission
        # order; entry-drained flows can neither block nor become HOL
        # during this advance, so the kernel's one-drain-per-UE-per-TTI
        # successor update walks exactly the oracle's first-undrained
        nxt = np.full(F, F, np.int64)
        is_hol0 = np.zeros(F + 1, np.bool_)
        live_idx = np.flatnonzero(was_live)
        lu = self._ue[:n][live_idx]
        order = np.lexsort((live_idx, lu))
        li, lg = live_idx[order], lu[order]
        if li.size:
            same = lg[1:] == lg[:-1]
            nxt[li[:-1][same]] = li[1:][same]
            head = np.ones(li.size, np.bool_)
            head[1:] = ~same
            is_hol0[li[head]] = True
        seg_size = np.bincount(seg, minlength=n_seg).astype(np.int64)
        es = np.sort(self._enq[:n][was_live])
        enq_sorted = np.full(_pad_len(es.size + 1), np.inf)
        enq_sorted[:es.size] = es
        tape = self.cell._tape
        with _x64():
            carry = (jnp.int64(_RUNNING), jnp.int64(self._k), jnp.int64(0),
                     jnp.int64(0), jnp.int64(self.cell._rr_ptr),
                     jnp.int64(0), jnp.asarray(pad(self._rem)),
                     jnp.asarray(pad(self._fin, np.nan)),
                     jnp.asarray(pad(self._grt)), jnp.asarray(pad(self._act)),
                     jnp.asarray(pad(self._ntx)), jnp.asarray(pad(self._nrx)),
                     jnp.asarray(np.concatenate(
                         [pfa, np.zeros(ue_pad - pfa.size)])
                         if pfa.size < ue_pad else pfa[:ue_pad]),
                     jnp.asarray(is_hol0), jnp.asarray(base_open),
                     jnp.int64(live_idx.size), jnp.int64(0))
            jenq = jnp.asarray(pad(self._enq, np.inf))
            jdead = jnp.asarray(pad(self._dead))
            jbpp = jnp.asarray(pad(self._bpp, 1.0))
            jue, jseg = jnp.asarray(ue), jnp.asarray(seg_p)
            jnxt = jnp.asarray(nxt)
            jsegsz = jnp.asarray(seg_size)
            jes = jnp.asarray(enq_sorted)
            oc = base_open
            for steps in _chunk_schedule(n):
                # per-TTI draw count == flows in still-open segments, a
                # bound the kernel can only shrink; fill exactly that
                nd_bound = int(seg_size[oc > 0].sum())
                tape.fill(harq_rng, steps * max(nd_bound, 1))
                valid = tape.buf.size
                # the kernel only ever tests u < bler, so pre-compare on
                # the host and ship 1-byte fail bits, not f64 uniforms
                pbuf = np.zeros(_pad_len(max(valid, 1), 1024), np.bool_)
                np.less(tape.buf, cfg.bler_target, out=pbuf[:valid])
                buf = jnp.asarray(pbuf)
                carry = _stream_chunk(
                    carry, jenq, jdead, jbpp, jue, jseg, jsegsz, jnxt,
                    jes, buf,
                    jnp.int64(valid), jnp.float64(cfg.tti_s),
                    jnp.int64(cfg.max_slots), jnp.float64(until_s),
                    steps=steps, n_prbs=cfg.n_prbs, policy=self.cell.policy)
                code = int(carry[0])
                tape.consume(int(carry[2]))
                carry = carry[:2] + (jnp.int64(0),) + carry[3:]
                oc = np.asarray(carry[14])
                if code == _TAPE_OUT:
                    carry = (jnp.int64(_RUNNING),) + carry[1:]
                    continue
                if code in (_DONE, _TIME_UP):
                    break
                if code == _SLOT_GUARD:
                    raise RuntimeError(
                        f"RanStream: uplink queues not drained after "
                        f"{cfg.max_slots} TTIs in one advance; raise "
                        f"RanConfig.max_slots or reduce the offered load")
            self._k = int(carry[1])
            self.cell._rr_ptr = int(carry[4])
            rem = np.asarray(carry[6])[:n]
            fin = np.asarray(carry[7])[:n]
            self._grt[:n] = np.asarray(carry[8])[:n]
            self._act[:n] = np.asarray(carry[9])[:n]
            self._ntx[:n] = np.asarray(carry[10])[:n]
            self._nrx[:n] = np.asarray(carry[11])[:n]
            if self.cell.policy == _PF:
                self.cell._pf_avg = np.asarray(carry[12])
        self._rem[:n] = rem
        self._fin[:n] = fin
        done_now = was_live & (rem <= 0.0)
        fidx = np.flatnonzero(done_now)
        # completion order: finish times rise with the TTI index and ties
        # within one TTI resolve in admission order -- the oracle's
        # append order
        fidx = fidx[np.lexsort((fidx, fin[fidx]))]
        finished = [self._flow_view(int(i)) for i in fidx]
        for i in fidx:
            c = int(self._coh[i])
            self._cohort_open[c] -= 1
            if self._cohort_open[c] == 0:
                del self._cohort_open[c]
        self._compact()
        return finished

    def _compact(self):
        """Twin of ``_retire``'s pruning: drop drained flows whose cohort
        has retired (left ``_cohort_open``)."""
        n = self._n
        if n == 0:
            return
        live = self._rem[:n] > 0.0
        keep = live | np.array([self._cohort_open.get(int(c), 0) > 0
                                for c in self._coh[:n]], bool)
        if keep.all():
            return
        kidx = np.flatnonzero(keep)
        for name in ("_ue", "_enq", "_dead", "_bpp", "_rem", "_fin",
                     "_grt", "_act", "_ntx", "_nrx", "_gaa", "_coh"):
            arr = getattr(self, name)
            arr[:kidx.size] = arr[kidx]
        self._meta = [self._meta[i] for i in kidx]
        self._reqs = [self._reqs[i] for i in kidx]
        self._n = kidx.size

    # -- handover ------------------------------------------------------------
    def migrate_ue(self, ue_id: int) -> List[StreamFlow]:
        n = self._n
        mine = np.flatnonzero((self._ue[:n] == ue_id)
                              & (self._rem[:n] > 0.0))
        flows = [self._flow_view(int(i)) for i in mine]
        if mine.size:
            for i in mine:
                c = int(self._coh[i])
                self._cohort_open[c] -= 1
                if self._cohort_open[c] == 0:
                    del self._cohort_open[c]
            keep = np.ones(n, bool)
            keep[mine] = False
            kidx = np.flatnonzero(keep)
            for name in ("_ue", "_enq", "_dead", "_bpp", "_rem", "_fin",
                         "_grt", "_act", "_ntx", "_nrx", "_gaa", "_coh"):
                arr = getattr(self, name)
                arr[:kidx.size] = arr[kidx]
            self._meta = [self._meta[i] for i in kidx]
            self._reqs = [self._reqs[i] for i in kidx]
            self._n = kidx.size
            self._compact()
        return flows

    def adopt(self, flow: StreamFlow, enqueue_s: float,
              cohort: int) -> StreamFlow:
        req = dataclasses.replace(flow.req, enqueue_s=enqueue_s)
        i = self._append(req, cohort, flow.meta, flow.rem_bits,
                         granted=flow.granted, act_slots=flow.act_slots,
                         n_tx=flow.n_tx, n_retx=flow.n_retx,
                         granted_at_admit=flow.granted)
        self._cohort_open[cohort] = self._cohort_open.get(cohort, 0) + 1
        return self._flow_view(i)

    # -- batched park/adopt (mass-blackout hot path) -------------------------
    def migrate_ues(self, ue_ids: Sequence[int],
                    flush_tb: bool = False) -> List["ParkedFlows"]:
        """Pop every live flow belonging to ``ue_ids`` with ONE array
        compaction (vs K× ``migrate_ue`` full rebuilds for a K-UE
        blackout).  Returns one ``ParkedFlows`` per requested UE, each
        in admission order -- the exact per-UE lists the oracle's
        ``migrate_ues`` produces, in array form.  ``flush_tb`` applies
        the blackout in-flight-TB loss rule vectorized."""
        n = self._n
        ids = np.asarray(list(ue_ids), np.int64)
        sel = (np.isin(self._ue[:n], ids) & (self._rem[:n] > 0.0))
        mine = np.flatnonzero(sel)
        batch = ParkedFlows(
            ue=self._ue[mine].copy(), bpp=self._bpp[mine].copy(),
            coh=self._coh[mine].copy(), rem=self._rem[mine].copy(),
            grt=self._grt[mine].copy(), act=self._act[mine].copy(),
            ntx=self._ntx[mine].copy(), nrx=self._nrx[mine].copy(),
            gaa=self._gaa[mine].copy(),
            reqs=[self._reqs[i] for i in mine],
            meta=[self._meta[i] for i in mine])
        if flush_tb:
            batch.flush_tb()
        if mine.size:
            for c, cnt in zip(*np.unique(batch.coh, return_counts=True)):
                c = int(c)
                self._cohort_open[c] -= int(cnt)
                if self._cohort_open[c] == 0:
                    del self._cohort_open[c]
            kidx = np.flatnonzero(~sel)
            for name in ("_ue", "_enq", "_dead", "_bpp", "_rem", "_fin",
                         "_grt", "_act", "_ntx", "_nrx", "_gaa", "_coh"):
                arr = getattr(self, name)
                arr[:kidx.size] = arr[kidx]
            self._meta = [self._meta[i] for i in kidx]
            self._reqs = [self._reqs[i] for i in kidx]
            self._n = kidx.size
            self._compact()
        return [batch.take(np.flatnonzero(batch.ue == u)) for u in ids]

    def _reserve(self, k: int):
        while self._n + k > self._cap:
            self._grow()

    def adopt_batch(self, parked: "ParkedFlows", enqueue_s: float,
                    cohort: int) -> "ParkedFlows":
        """Re-admit a parked batch at recovery with slice assignment --
        the array twin of per-flow ``adopt``.  Each flow's enqueue
        becomes ``max(original, enqueue_s)`` (a flow parked before it
        would have entered keeps its own entry time), counters carry,
        and ``granted_at_admit`` snapshots the accumulated grant, all
        matching the oracle's ``adopt_batch`` field-for-field."""
        k = len(parked)
        if k == 0:
            return parked
        self._reserve(k)
        i0 = self._n
        sl = slice(i0, i0 + k)
        reqs = [dataclasses.replace(r, enqueue_s=max(r.enqueue_s, enqueue_s))
                for r in parked.reqs]
        self._ue[sl] = parked.ue
        self._enq[sl] = [r.enqueue_s for r in reqs]
        self._dead[sl] = [r.deadline_s for r in reqs]
        # scalar per-request bits_per_prb, matching _append bit-for-bit
        self._bpp[sl] = [float(self.cell.bits_per_prb(r.link_rate_bps))
                         for r in reqs]
        self._rem[sl] = parked.rem
        self._fin[sl] = np.nan
        self._grt[sl] = parked.grt
        self._act[sl] = parked.act
        self._ntx[sl] = parked.ntx
        self._nrx[sl] = parked.nrx
        self._gaa[sl] = parked.grt
        self._coh[sl] = cohort
        self._meta.extend(parked.meta)
        self._reqs.extend(reqs)
        self._n = i0 + k
        self._cohort_open[cohort] = self._cohort_open.get(cohort, 0) + k
        return parked

    def report(self, flow: StreamFlow) -> GrantReport:
        cfg = self.cfg
        tx_s = float(flow.finish_s - flow.req.enqueue_s)
        return GrantReport(
            ue_id=flow.req.ue_id, n_bytes=flow.req.n_bytes,
            enqueue_s=flow.req.enqueue_s, finish_s=float(flow.finish_s),
            tx_s=tx_s, granted_prbs=flow.granted,
            active_slots=flow.act_slots, n_tx=flow.n_tx,
            n_harq_retx=flow.n_retx,
            realized_rate_bps=(flow.req.n_bytes * 8.0 / tx_s
                               if tx_s > 0 else 0.0),
            prb_share=(flow.granted / (cfg.n_prbs * flow.act_slots)
                       if flow.act_slots else 0.0),
            mcs=int(mcs_index_vec(flow.bpp)))

    @property
    def backlog_bytes(self) -> float:
        n = self._n
        live = self._rem[:n] > 0.0
        return float(self._rem[:n][live].sum() / 8.0)

    def telemetry_sample(self) -> Dict[str, float]:
        """Twin of ``RanStream.telemetry_sample``: the identical
        observation read from the array state (one vectorized pass, so
        sampling a 10k-flow stream costs microseconds, not a python
        loop).  Values match the oracle's field-for-field."""
        n = self._n
        live = self._rem[:n] > 0.0
        return {"tti": float(self._k),
                "backlog_bytes": float(self._rem[:n][live].sum() / 8.0),
                "live_flows": float(int(live.sum())),
                "open_cohorts": float(len(self._cohort_open))}
