"""Involuntary preemption: spill/restore round trips (dense + paged +
int8 + mid-prefill), stop-decision invariance under forced preemption
across policy x packing x paging, page-ownership invariants, SWAPPED
re-admission ordering, victim selection, EDF admission, and the
oversized-gang skip (a blocked gang no longer stalls singletons)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.probe import ProbeConfig, init_outer
from repro.models import build
from repro.serving import (ChunkSeg, ChunkWork, ContinuousServingEngine,
                           EDFPolicy, FIFOPolicy, OrcaScheduler,
                           RequestState, ServeConfig, make_request,
                           replay_model, replay_params, replay_requests,
                           served_stop_times)

from tests._hypothesis_stub import given, settings, st


# ---------------------------------------------------------------------------
# engine-level: preempt -> restore is bit-for-bit

def _probe_row(state, slot):
    return {f: np.asarray(getattr(state, f)[slot]) for f in state._fields}


def _rows_equal(a, b, msg):
    for f, v in a.items():
        np.testing.assert_array_equal(v, b[f], err_msg=f"{msg}: {f}")


def _replay_engine(bank, n_slots=3):
    pc = ProbeConfig(d_phi=bank.shape[2], smooth_window=3)
    theta = init_outer(pc, jax.random.PRNGKey(1))
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=bank.shape[1],
                      lam=0.9, burn_in=1)
    return ContinuousServingEngine(replay_model(bank), replay_params(bank),
                                   pc, theta, cfg, n_slots=n_slots,
                                   cache_len=bank.shape[1] + 2)


def test_dense_spill_restore_bit_for_bit():
    """Dense engine: a preempted slot's Spill captures the probe row
    exactly, and restoring it into a DIFFERENT slot replays the identical
    future — smoothed scores, counters and stop flags, bit for bit."""
    rs = np.random.RandomState(0)
    bank = (rs.randn(4, 20, 16) * 0.5).astype(np.float32)
    eng_a, eng_b = _replay_engine(bank), _replay_engine(bank)
    for eng in (eng_a, eng_b):
        eng.admit(0, {"tokens": jnp.full((1, 1), 0, jnp.int32)}, 1)
        eng.admit(1, {"tokens": jnp.full((1, 1), 1, jnp.int32)}, 1)
        for _ in range(5):
            eng.step()
    before = _probe_row(eng_a.st, 0)
    pos_before, tok_before = int(eng_a.pos[0]), int(eng_a.token[0])
    spill = eng_a.preempt(0)
    assert spill.armed and spill.pages is None and spill.lane is not None
    assert spill.pos == pos_before and spill.token == tok_before
    assert spill.nbytes > 0
    _rows_equal(dict(zip(eng_a.st._fields, map(np.asarray, spill.probe))),
                before, "spill.probe")
    assert bool(eng_a.st.stopped[0])          # the slot is parked
    eng_a.restore(2, spill)                   # a different physical slot
    _rows_equal(_probe_row(eng_a.st, 2), before, "restored row")
    assert int(eng_a.pos[2]) == pos_before
    for i in range(12):
        va, vb = eng_a.step(), eng_b.step()
        for f in ("smoothed", "n_scores", "stopped", "stop_step", "tokens"):
            np.testing.assert_array_equal(
                getattr(va, f)[2], getattr(vb, f)[0],
                err_msg=f"step {i}: {f} diverged after restore")
            np.testing.assert_array_equal(
                getattr(va, f)[1], getattr(vb, f)[1],
                err_msg=f"step {i}: {f} of the UNDISTURBED slot moved")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("smollm_360m").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _paged_engine(model, params, *, chunk_tokens=None, num_blocks=16):
    pc = ProbeConfig(d_phi=model.cfg.d_model, smooth_window=2)
    theta = init_outer(pc, jax.random.PRNGKey(1))
    theta["b0"] = jnp.asarray(3.0)
    cfg = ServeConfig(tokens_per_step=2, max_new_tokens=10, lam=2.0,
                      burn_in=1)
    return ContinuousServingEngine(model, params, pc, theta, cfg,
                                   n_slots=2, cache_len=18, paged=True,
                                   block_size=4, num_blocks=num_blocks,
                                   chunk_tokens=chunk_tokens)


def _paged_roundtrip(model, params):
    """Preempt slot 0 mid-decode and restore it onto DIFFERENT physical
    pages; its future must match an undisturbed twin bit for bit."""
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                                 model.cfg.vocab_size)
    eng_a = _paged_engine(model, params)
    eng_b = _paged_engine(model, params)
    row0, row1, row_new = [1, 2, 3, 4], [5, 6, 7, 8], [12, 9, 11, 10]
    for eng in (eng_a, eng_b):
        eng.admit(0, {"tokens": prompts[0:1]}, 6, block_row=row0)
        eng.admit(1, {"tokens": prompts[1:2]}, 6, block_row=row1)
        for _ in range(3):
            eng.step()
    before = _probe_row(eng_a.st, 0)
    spill = eng_a.preempt(0, block_row=row0)
    assert spill.pages is not None and spill.n_blocks == 4
    assert spill.nbytes > 0
    # only the table indirection changes: new (even reordered) pages
    eng_a.restore(0, spill, block_row=row_new)
    _rows_equal(_probe_row(eng_a.st, 0), before, "restored row")
    for i in range(5):
        va, vb = eng_a.step(), eng_b.step()
        for f in ("smoothed", "n_scores", "stopped", "stop_step", "tokens"):
            np.testing.assert_array_equal(
                getattr(va, f), getattr(vb, f),
                err_msg=f"step {i}: {f} diverged after page move")
    return spill


def test_paged_spill_restore_bit_for_bit(small_model):
    model, params = small_model
    _paged_roundtrip(model, params)


def test_paged_int8_spill_restore_bit_for_bit():
    """Quantized KV spills carry the per-page scales with the pages."""
    cfg = dataclasses.replace(get_config("smollm_360m").reduced(),
                              kv_cache_dtype="int8")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spill = _paged_roundtrip(model, params)
    assert {"k", "v", "k_scale", "v_scale"} <= set(spill.pages)


def test_mid_prefill_spill_restore_bit_for_bit(small_model):
    """A victim preempted BETWEEN prefill chunks (probe parked, table row
    still NULL) resumes on new pages and decodes the identical future."""
    model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0,
                                model.cfg.vocab_size)
    tokens = np.asarray(prompt[0])
    eng_a = _paged_engine(model, params, chunk_tokens=4)
    eng_b = _paged_engine(model, params, chunk_tokens=4)
    row_a, row_new = [1, 2, 3, 4], [8, 7, 6, 5]

    def chunk(row, start):
        return ChunkWork(segs=(ChunkSeg(slot=0, tokens=tokens, start=start,
                                        length=4,
                                        row=np.asarray(row, np.int32)),))

    for eng in (eng_a, eng_b):
        eng.begin_prefill(0)
        eng.step(chunk(row_a, 0))             # first half of the prompt
    spill = eng_a.preempt(0, block_row=row_a, armed=False, prompt_len=4)
    assert not spill.armed and spill.prompt_len == 4
    eng_a.restore(0, spill, block_row=row_new)
    assert bool(eng_a.st.stopped[0])          # still parked mid-prefill
    eng_a.step(chunk(row_new, 4))             # second half, new pages
    eng_b.step(chunk(row_a, 4))
    eng_a.finish_prefill(0, {"tokens": prompt}, 8, block_row=row_new)
    eng_b.finish_prefill(0, {"tokens": prompt}, 8, block_row=row_a)
    for i in range(5):
        va, vb = eng_a.step(), eng_b.step()
        for f in ("smoothed", "n_scores", "stopped", "stop_step", "tokens"):
            np.testing.assert_array_equal(
                getattr(va, f)[0], getattr(vb, f)[0],
                err_msg=f"step {i}: {f} diverged after mid-prefill spill")


# ---------------------------------------------------------------------------
# scheduler-level: forced preemption never moves a stop decision

N_TRAJ, T_STEPS, D_PHI = 9, 24, 16


@pytest.fixture(scope="module")
def replay_bank():
    rs = np.random.RandomState(0)
    drift = np.linspace(0, 1.2, T_STEPS)[None, :, None]
    bank = (rs.randn(N_TRAJ, T_STEPS, D_PHI) * 0.3
            + drift * rs.rand(N_TRAJ, 1, D_PHI)).astype(np.float32)
    theta = {"W0": (rs.randn(D_PHI) * 0.4).astype(np.float32),
             "b0": np.float32(-0.2)}
    return bank, theta


def _fleet(bank, theta, *, n_slots=3, paged=True, num_blocks=None,
           chunk_tokens=None, policy=None, pack_chunks=False,
           preemption=True, priorities=None, group=None, deadlines=None):
    pc = ProbeConfig(d_phi=D_PHI, smooth_window=4)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=T_STEPS, lam=0.62,
                      burn_in=3)
    sched = OrcaScheduler(replay_model(bank), replay_params(bank), pc, theta,
                          cfg, n_slots=n_slots, paged=paged, block_size=4,
                          num_blocks=num_blocks, chunk_tokens=chunk_tokens,
                          pack_chunks=pack_chunks, policy=policy,
                          preemption=preemption)
    reqs = replay_requests([T_STEPS] * bank.shape[0])
    for i, r in enumerate(reqs):
        r.priority = (priorities[i] if priorities is not None else i % 2)
        if deadlines is not None:
            r.deadline_ms = deadlines[i]
        if group is not None and i in group:
            r.group_id, r.sample_idx = 0, group.index(i)
    return sched, reqs


# Two class layouts that force preemption deterministically:
#
# * BURST (for FIFO, which ignores class at admission): batch traffic
#   arrives first and fills every slot, then two urgent requests hit a
#   full fleet — each spills the newest batch resident.
# * GANG (for priority/EDF, which admit urgent work first so a burst
#   never contends): an urgent singleton whose trajectory stops EARLY
#   shares the fleet with low-class traffic while a mid-class gang of 3
#   waits; the freed slot is not enough for the gang, so it preempts the
#   low-class residents to complete its slot quota.
BURST_PRIO = [1, 1, 1, 0, 0, 2, 2, 2, 2]
GANG_PRIO = [1, 0, 2, 2, 1, 1, 2, 2, 2]
GANG = [0, 4, 5]
BLOCKS_PER_REQ = 7                     # ceil((1 + 24) / 4)


def _layout(policy):
    return ((BURST_PRIO, None) if policy == "fifo"
            else (GANG_PRIO, GANG))


@pytest.fixture(scope="module")
def abundant_tau(replay_bank):
    bank, theta = replay_bank
    sched, reqs = _fleet(bank, theta, n_slots=N_TRAJ,
                         num_blocks=1 + N_TRAJ * BLOCKS_PER_REQ)
    done, fleet = sched.run(reqs)
    assert fleet.preemptions == 0      # nothing contended: pure baseline
    tau = served_stop_times(done, [T_STEPS] * N_TRAJ)
    assert 0 < int((tau < T_STEPS).sum()) < N_TRAJ   # real mixed stops
    return tau


@pytest.mark.parametrize("paged,chunk,policy,pack", [
    (True, None, "fifo", False),
    (True, 3, "fifo", False),
    (False, None, "fifo", False),
    (True, None, "priority", False),
    (True, 3, "priority", True),
    (False, None, "priority", False),
    (True, None, "edf", False),
    (False, 3, "edf", True),
])
def test_forced_preemption_is_stop_invariant(replay_bank, abundant_tau,
                                             paged, chunk, policy, pack):
    """A fleet under REAL contention (>= 1 victim spilled AND restored)
    serves byte-identical stop decisions to the abundant run — across
    victim-selection policy, chunk packing and paged/dense engines."""
    bank, theta = replay_bank
    priorities, group = _layout(policy)
    sched, reqs = _fleet(bank, theta, n_slots=3, paged=paged,
                         num_blocks=1 + 3 * BLOCKS_PER_REQ,
                         chunk_tokens=chunk, policy=policy,
                         pack_chunks=pack, priorities=priorities,
                         group=group)
    done, fleet = sched.run(reqs)
    assert all(r.done for r in done)
    assert fleet.preemptions > 0, "contention never materialized (vacuous)"
    assert fleet.restores == fleet.preemptions
    if paged:
        assert fleet.spilled_blocks > 0
        assert sched.pool.num_free == sched.pool.num_usable
        sched.pool.check()
    np.testing.assert_array_equal(
        served_stop_times(done, [T_STEPS] * N_TRAJ), abundant_tau)
    # a preempted request went through SWAPPED and came back
    victims = [r for r in done if r.n_preempted > 0]
    assert victims
    for r in victims:
        assert r.restored_step > r.admitted_step
        assert r.state in (RequestState.STOPPED, RequestState.FINISHED)


def test_preemption_off_is_wait_only(replay_bank, abundant_tau):
    bank, theta = replay_bank
    sched, reqs = _fleet(bank, theta, n_slots=3,
                         num_blocks=1 + 3 * BLOCKS_PER_REQ,
                         priorities=BURST_PRIO, preemption=False)
    done, fleet = sched.run(reqs)
    assert fleet.preemptions == 0 and fleet.restores == 0
    assert all(r.n_preempted == 0 for r in done)
    np.testing.assert_array_equal(
        served_stop_times(done, [T_STEPS] * N_TRAJ), abundant_tau)


@given(seed=st.integers(min_value=0, max_value=10_000),
       policy=st.sampled_from(["fifo", "priority", "edf"]),
       paged=st.booleans(), pack=st.booleans())
@settings(max_examples=10, deadline=None)
def test_fuzz_preemption_no_double_ownership(seed, policy, paged, pack):
    """Random priorities/deadlines under a tight pool: every page has one
    owner at a time (pool.check() after every terminal state), every
    request terminates, and a spilled request's pages are back in the pool
    while it sits SWAPPED."""
    rs = np.random.RandomState(seed)
    bank = (rs.randn(7, 16, 8) * 0.4
            + np.linspace(0, 1, 16)[None, :, None]).astype(np.float32)
    theta = {"W0": (rs.randn(8) * 0.4).astype(np.float32),
             "b0": np.float32(-0.1)}
    pc = ProbeConfig(d_phi=8, smooth_window=3)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=16, lam=0.6,
                      burn_in=2)
    per_req = (1 + 16 + 3) // 4
    sched = OrcaScheduler(replay_model(bank), replay_params(bank), pc, theta,
                          cfg, n_slots=3, paged=paged, block_size=4,
                          num_blocks=1 + 2 * per_req,
                          chunk_tokens=(3 if pack else None),
                          pack_chunks=pack, policy=policy)
    reqs = replay_requests([16] * 7)
    for r in reqs:
        r.priority = int(rs.randint(0, 3))
        if rs.rand() < 0.5:
            r.deadline_ms = float(rs.randint(50, 500))
    done, fleet = sched.run(reqs)
    assert all(r.done for r in done)
    if paged:                                      # dense runs own no pool
        # every page returned: a finished request's block_ids stay as the
        # record of what it held, each page back at refcount 0
        assert all(sched.pool.refcount(b) == 0
                   for r in done for b in r.block_ids)
        assert sched.pool.num_free == sched.pool.num_usable
        sched.pool.check()
    assert fleet.restores == fleet.preemptions


# ---------------------------------------------------------------------------
# SWAPPED queue ordering + victim selection

def test_swapped_restores_before_waiting(replay_bank):
    """Victims spilled for an urgent gang restore BEFORE any same-class
    WAITING request is admitted — the SWAPPED queue outranks WAITING."""
    bank, theta = replay_bank
    sched, reqs = _fleet(bank, theta, n_slots=2,
                         num_blocks=1 + 4 * BLOCKS_PER_REQ,
                         priorities=[1, 0, 0, 1, 1, 1, 1, 1, 1],
                         group=[1, 2])
    done, fleet = sched.run(reqs)
    assert fleet.preemptions >= 1      # the gang evicted the resident
    victims = [r for r in done if r.n_preempted > 0]
    assert victims
    fresh = [r for r in done
             if r.n_preempted == 0 and r.priority == 1
             and r.admitted_step > 0 and r.group_id is None]
    assert fresh, "no class-1 admission followed the restores"
    for v in victims:
        assert v.restored_step >= 0
        assert all(v.restored_step <= w.admitted_step for w in fresh), \
            "a WAITING request overtook a SWAPPED victim of its own class"


def test_select_victim_lowest_class_newest_first():
    pol = FIFOPolicy()
    res = [make_request(np.zeros(1, np.int64), priority=p)
           for p in (2, 1, 2, 0)]
    for i, r in enumerate(res):
        r.admitted_step = i
    # for a class-0 admission: class 2 outranks class 1, newest class-2 wins
    assert pol.select_victim(res, 0) == 2
    # for a class-1 admission only the class-2 residents are eligible
    assert pol.select_victim(res, 1) == 2
    res[2].priority = 0
    assert pol.select_victim(res, 1) == 0
    # equal-or-higher urgency is never preempted (DAG: no livelock)
    assert pol.select_victim(res, 2) is None


def test_edf_ranks_by_deadline_and_from_metrics():
    reqs = [make_request(np.zeros(1, np.int64), priority=p)
            for p in (0, 1, 2)]
    # explicit per-request deadline beats any class SLO
    reqs[2].deadline_ms = 10.0
    pol = EDFPolicy(class_slo_ms={0: 500.0, 1: 200.0})
    assert pol.select_admit(reqs, 0) == 2
    reqs[2].deadline_ms = None
    # class SLOs: class 1 (200ms) now outranks class 0 (500ms)
    assert pol.select_admit(reqs, 0) == 1
    # unknown class 2 falls back to default_slo_ms * (priority + 1)
    assert pol._deadline(reqs[2]) == pytest.approx(3000.0)
    # the observability loop: SLOs seeded from a run's per-class p99s
    per_class = {"c0_ttft_ms_p99": 80.0, "c1_ttft_ms_p99": 40.0,
                 "c0_queue_wait_ms_p99": 999.0}        # non-TTFT key ignored
    pol2 = EDFPolicy.from_metrics(per_class, slack=1.5)
    assert pol2.class_slo_ms == {0: pytest.approx(120.0),
                                 1: pytest.approx(60.0)}
    assert pol2.select_admit(reqs[:2], 0) == 1


# ---------------------------------------------------------------------------
# oversized-gang admission (the run-loop `break` bugfix)

def _gang_fleet(bank, theta, *, max_head_skips=8, preemption=False):
    pc = ProbeConfig(d_phi=D_PHI, smooth_window=4)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=T_STEPS, lam=0.62,
                      burn_in=3)
    sched = OrcaScheduler(replay_model(bank), replay_params(bank), pc, theta,
                          cfg, n_slots=3, paged=True, block_size=4,
                          num_blocks=1 + 6 * BLOCKS_PER_REQ,
                          policy=FIFOPolicy(max_head_skips=max_head_skips),
                          preemption=preemption)
    reqs = replay_requests([T_STEPS] * bank.shape[0])
    # queue order: 2 singletons, then a gang of 3, then more singletons —
    # the gang can only start once a whole fleet's worth of slots is free
    for i in (2, 3, 4):
        reqs[i].group_id, reqs[i].sample_idx = 0, i - 2
    return sched, reqs


def test_singleton_admits_past_blocked_gang(replay_bank):
    """FIFO, no preemption: requests 0-1 occupy 2 of 3 slots, the gang of
    3 cannot start — the old composer loop would `break` and leave the
    free slot idle forever.  The policy skip admits the singletons behind
    the gang into the free slot while the gang waits its turn."""
    bank, theta = replay_bank
    sched, reqs = _gang_fleet(bank, theta)
    done, fleet = sched.run(reqs)
    assert all(r.done for r in done) and fleet.preemptions == 0
    gang = [r for r in done if r.group_id is not None]
    solo_late = [r for r in done if r.group_id is None and r.req_id
                 > max(g.req_id for g in gang)]
    # a later singleton used the slot the blocked gang could not
    assert min(s.admitted_step for s in solo_late) \
        < min(g.admitted_step for g in gang)
    # gang admission stayed atomic: all samples entered on one step
    assert len({g.admitted_step for g in gang}) == 1
    # and the skip moved WHEN work happened, never what the probe saw
    solo_sched, solo_reqs = _gang_fleet(bank, theta)
    for r in solo_reqs:
        r.group_id = None
    solo_done, _ = solo_sched.run(solo_reqs)
    np.testing.assert_array_equal(
        served_stop_times(done, [T_STEPS] * N_TRAJ),
        served_stop_times(solo_done, [T_STEPS] * N_TRAJ))


def test_blocked_gang_ages_to_a_pin(replay_bank):
    """With max_head_skips=1 the gang is pinned after one skip: every
    still-waiting singleton must then queue BEHIND it."""
    bank, theta = replay_bank
    sched, reqs = _gang_fleet(bank, theta, max_head_skips=1)
    done, fleet = sched.run(reqs)
    assert all(r.done for r in done)
    gang_step = min(r.admitted_step for r in done if r.group_id is not None)
    overtakers = [r for r in done
                  if r.group_id is None and 0 < r.admitted_step < gang_step]
    assert len(overtakers) <= 1        # the single allowed skip, no more
