"""Group serving: gang admission, conformal consensus stop, mid-flight
sibling cancellation — and the schedule-invariance contract (the group
layer is INERT for ungrouped or consensus-off fleets: stop decisions are
byte-identical to the classic engine under every policy/packing/paging
configuration)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as orca
from repro.configs import get_config
from repro.core import stopping as S
from repro.core.calibrator import GroupCalibrator, GroupTrace
from repro.core.probe import ProbeConfig, init_outer
from repro.models import build
from repro.serving import (OrcaScheduler, RequestState, ServeConfig,
                           group_requests, make_group, make_group_fleet,
                           make_request, replay_model, replay_params)
from repro.trajectories.synthetic import TrajectoryDistribution, generate
from tests._hypothesis_stub import given, settings, st

D = 24


def _bank(n, t, seed=0, scale=0.6):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, t, D) * scale).astype(np.float32)


def _probe(bias, smooth_window=1, d=D):
    pc = ProbeConfig(d_phi=d, smooth_window=smooth_window)
    theta = init_outer(pc, jax.random.PRNGKey(1))
    theta["b0"] = jnp.asarray(float(bias))
    return pc, theta


def _replay_reqs(n, lengths, *, group_size=None, prompt_len=1):
    """Replay requests; ``group_size`` assigns consecutive group ids."""
    reqs = []
    for i in range(n):
        gid = (i // group_size) if group_size else None
        sj = (i % group_size) if group_size else 0
        reqs.append(make_request(np.full((prompt_len,), i, np.int64),
                                 max_new_tokens=int(lengths[i]),
                                 group_id=gid, sample_idx=sj))
    return reqs


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("smollm_360m").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ---------------------------------------------------------------------------
# consensus math (core.stopping)

def test_weighted_vote_tie_breaks_toward_smaller_hash():
    ans, agr = S.weighted_vote([1.0, 1.0], [5, 3], [True, True])
    assert (ans, agr) == (3, 0.5)


def test_weighted_vote_inactive_and_nonpositive():
    assert S.weighted_vote([0.9, 0.9], [1, 2], [False, False]) == (-1, 0.0)
    # negative confidences are clipped, not sign-flipped into votes
    ans, agr = S.weighted_vote([-1.0, 0.5], [7, 2], [True, True])
    assert (ans, agr) == (2, 1.0)


def test_consensus_trace_freezes_votes_at_stop_and_length():
    # sample 0 stops at tau=1 (keeps voting answer 8 with score 0.9);
    # sample 1 runs to its length-2 trajectory end then freezes
    scores = np.array([[0.2, 0.9, 0.1, 0.1],
                       [0.3, 0.3, 0.0, 0.0]])
    answers = np.array([[7, 8, 9, 9],
                        [8, 8, 0, 0]])
    lengths = np.array([4, 2])
    ans, agr = S.consensus_trace(scores, answers, lengths,
                                 per_sample_tau=np.array([1, 10]))
    # t=0: votes (7@.2, 8@.3) -> 8; t>=1: both frozen on 8
    assert ans.tolist() == [8, 8, 8, 8]
    np.testing.assert_allclose(agr[1:], 1.0)


def test_consensus_stop_times_burn_in_and_never():
    agr = np.array([1.0, 1.0, 0.0, 0.95])
    taus = S.consensus_stop_times(agr, [0.9, 2.0], burn_in=2)
    assert taus.tolist() == [3, 4]      # first crossing >= burn-in; never=Tg


def test_consensus_risk_charges_only_wrong_fires():
    tau_g = np.array([2, 4, 3])          # Tg=4: group 1 never fired
    ans = np.array([[5, 5, 5, 5], [1, 1, 1, 1], [9, 9, 9, 9]])
    risk = [float(S.consensus_risk(np.array([t]), a, truth=5)[0])
            for t, a in zip(tau_g, ans)]
    assert risk == [0.0, 0.0, 1.0]


# ---------------------------------------------------------------------------
# GroupCalibrator

def test_group_calibrator_threshold_requires_calibrate():
    with pytest.raises(RuntimeError, match="calibrate"):
        GroupCalibrator().threshold()


def test_group_calibrator_decide_gates():
    gc = GroupCalibrator(min_votes=2, burn_in=2, lam=0.6)
    # a lone voter never fires, however confident
    fire, _, _ = gc.decide([[0.9, 0.9, 0.9]], [[4, 4, 4]])
    assert not fire
    # two agreeing voters before burn-in: gated
    fire, _, _ = gc.decide([[0.9], [0.9]], [[4], [4]])
    assert not fire
    # past burn-in with full agreement: fires with the right answer
    fire, ans, agr = gc.decide([[0.9, 0.9, 0.9], [0.8, 0.8, 0.8]],
                               [[4, 4, 4], [4, 4, 4]])
    assert fire and ans == 4 and agr == pytest.approx(1.0)
    # split vote below threshold: no fire
    fire, _, agr = gc.decide([[0.5, 0.5, 0.5], [0.5, 0.5, 0.5]],
                             [[4, 4, 4], [9, 9, 9]])
    assert not fire and agr == pytest.approx(0.5)


def test_group_calibrator_calibrate_controls_group_risk():
    rs = np.random.RandomState(3)
    t, n, delta = 20, 3, 0.5
    traces = []
    for g in range(20):
        scores = rs.rand(n, t) * 0.5 + 0.4
        # one adversarial group votes a wrong answer unanimously; the rest
        # vote their truth from the start
        truth, vote = (g, 99) if g == 0 else (g, g)
        answers = np.full((n, t), vote)
        traces.append(GroupTrace(scores=scores, answers=answers,
                                 lengths=np.full(n, t), truth=truth))
    gc = GroupCalibrator(min_votes=2, burn_in=2)
    lam = gc.calibrate(traces, delta, eps=0.2)
    assert np.isfinite(lam) and gc.delta == delta
    fired_wrong = 0
    for tr in traces:
        a, g = S.consensus_trace(tr.scores, tr.answers, tr.lengths)
        tau = S.consensus_stop_times(g, [lam], burn_in=2)[0]
        fired_wrong += int(tau < t and a[tau] != tr.truth)
    assert fired_wrong / len(traces) <= delta


# ---------------------------------------------------------------------------
# group_requests partitioning

def test_group_requests_units_keep_arrival_order():
    g0 = make_group(np.zeros(4, np.int64), 2, group_id=0)
    solo = make_request(np.ones(4, np.int64))
    g1 = make_group(np.zeros(4, np.int64), 2, group_id=1)
    units, groups = group_requests([g0[0], solo, g0[1], g1[0], g1[1]])
    assert [len(u) for u in units] == [2, 1, 2]
    assert units[0] == g0 and units[1] == [solo] and units[2] == g1
    assert {g.group_id for g in groups} == {0, 1}


def test_group_requests_renumbers_duplicate_sample_idx():
    reqs = [make_request(np.zeros(2, np.int64), group_id=5)
            for _ in range(3)]                     # all sample_idx=0
    units, groups = group_requests(reqs)
    assert len(units) == 1 and groups[0].size == 3
    assert sorted(r.sample_idx for r in reqs) == [0, 1, 2]


# ---------------------------------------------------------------------------
# validation errors (scheduler + api facade) name the fix

def test_scheduler_rejects_bad_consensus_values():
    args = (None, None, ProbeConfig(d_phi=4), None, ServeConfig(lam=0.5))
    with pytest.raises(ValueError, match="not a threshold"):
        OrcaScheduler(*args, consensus=True)
    with pytest.raises(ValueError, match=r"outside \(0, 1\]"):
        OrcaScheduler(*args, consensus=1.5)
    with pytest.raises(ValueError, match="no threshold"):
        OrcaScheduler(*args, consensus=GroupCalibrator())
    with pytest.raises(ValueError, match="must be a GroupCalibrator"):
        OrcaScheduler(*args, consensus="0.9")


def test_scheduler_rejects_group_larger_than_fleet():
    bank = _bank(3, 4)
    pc, theta = _probe(0.0)
    sched = OrcaScheduler(replay_model(bank), replay_params(bank), pc, theta,
                          ServeConfig(tokens_per_step=1, max_new_tokens=4,
                                      lam=2.0),
                          n_slots=2)
    with pytest.raises(ValueError, match="gang admission"):
        sched.run(_replay_reqs(3, [4, 4, 4], group_size=3))


def test_api_engine_validates_group_knobs():
    dummy = object()                  # errors fire before serving_params()
    with pytest.raises(ValueError, match="group_size"):
        orca.engine(None, None, dummy, group_size=0)
    with pytest.raises(ValueError, match="raising n_slots"):
        orca.engine(None, None, dummy, n_slots=2, group_size=3)
    with pytest.raises(ValueError, match="group_size >= 2"):
        orca.engine(None, None, dummy, group_size=1, consensus=0.9)
    with pytest.raises(ValueError, match="consensus_delta"):
        orca.engine(None, None, dummy, group_size=2,
                    consensus_delta=0.1)
    stale = GroupCalibrator(lam=0.7)
    stale.delta = 0.2
    with pytest.raises(ValueError, match="does not match"):
        orca.engine(None, None, dummy, group_size=2, consensus=stale,
                    consensus_delta=0.3)


# ---------------------------------------------------------------------------
# schedule invariance: gang scheduling w/o consensus is byte-inert

@pytest.mark.parametrize("policy", ["fifo", "priority", "ttft"])
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("paged", [False, True])
def test_grouping_without_consensus_is_byte_inert(policy, pack, paged):
    """{fifo,priority,ttft} x {packed,unpacked} x {paged,dense}: the same
    fleet served ungrouped and as gang-scheduled (consensus-off) groups
    produces identical stops, scores and tokens, request for request."""
    n, t = 9, 12
    bank = _bank(n, t, seed=4)
    lengths = [12, 8, 10, 12, 6, 12, 9, 12, 7]
    pc, theta = _probe(1.0, smooth_window=2)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=0.62,
                      burn_in=2)

    def run(group_size):
        sched = OrcaScheduler(replay_model(bank), replay_params(bank),
                              pc, theta, cfg, n_slots=4, paged=paged,
                              block_size=4, chunk_tokens=3,
                              pack_chunks=pack, policy=policy)
        reqs = _replay_reqs(n, lengths, group_size=group_size)
        for i, r in enumerate(reqs):
            r.priority = i % 2
        done, fleet = sched.run(reqs)
        return done, fleet

    base, fleet_b = run(None)
    grouped, fleet_g = run(3)
    for rb, rg in zip(base, grouped):
        assert rb.stop_step == rg.stop_step
        assert rb.tokens == rg.tokens
        np.testing.assert_allclose(np.array(rb.scores),
                                   np.array(rg.scores), atol=1e-6)
        assert rg.state in (RequestState.STOPPED, RequestState.FINISHED)
    assert fleet_g.samples_cancelled == 0 and fleet_g.consensus_groups == 0


def test_singleton_groups_match_ungrouped_oracle():
    """group_size=1 (every request its own group) is the classic engine."""
    n, t = 6, 10
    bank = _bank(n, t, seed=9)
    pc, theta = _probe(1.2, smooth_window=2)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=0.6,
                      burn_in=1)

    def run(group_size):
        sched = OrcaScheduler(replay_model(bank), replay_params(bank),
                              pc, theta, cfg, n_slots=3, paged=True,
                              block_size=4)
        done, _ = sched.run(_replay_reqs(n, [t] * n,
                                         group_size=group_size))
        return done

    for rb, rg in zip(run(None), run(1)):
        assert rb.stop_step == rg.stop_step and rb.tokens == rg.tokens


# ---------------------------------------------------------------------------
# gang admission

def test_gang_admission_is_atomic():
    """All samples of a group land on the SAME engine step — a group is
    never half-resident, even when slots free up one at a time."""
    n, t = 9, 8
    bank = _bank(n, t, seed=5)
    pc, theta = _probe(0.0)                       # no stops: budget path
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=2.0)
    sched = OrcaScheduler(replay_model(bank), replay_params(bank), pc, theta,
                          cfg, n_slots=4, paged=True, block_size=4)
    # skewed budgets: slots return one by one, the next gang must wait for 3
    lengths = [8, 5, 3, 8, 8, 8, 8, 8, 8]
    done, _ = sched.run(_replay_reqs(n, lengths, group_size=3))
    units, groups = group_requests(done)
    for g in groups:
        steps = {r.admitted_step for r in g.requests}
        assert len(steps) == 1, f"group {g.group_id} split: {steps}"
    # distinct slots while co-resident
    for a, b in itertools.combinations(done, 2):
        if a.slot == b.slot:
            assert (a.completed_step <= b.admitted_step
                    or b.completed_step <= a.admitted_step)


def test_intra_gang_prompt_sharing(small_model):
    """Siblings share the leader's freshly-reserved full prompt pages by
    refcount (the group is its own prefix donor on a cold registry)."""
    model, params = small_model
    pc, theta = _probe(0.0, d=model.cfg.d_model)
    cfg = ServeConfig(tokens_per_step=2, max_new_tokens=8, lam=2.0,
                      burn_in=0)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (8,), 0,
                                model.cfg.vocab_size)
    sched = OrcaScheduler(model, params, pc, theta, cfg, n_slots=3,
                          paged=True, block_size=4)
    done, fleet = sched.run(make_group(prompt, 3, group_id=0))
    leader, *sibs = sorted(done, key=lambda r: r.sample_idx)
    assert not leader.prefill_skipped and leader.n_shared_blocks == 0
    for s in sibs:
        assert s.prefill_skipped and s.n_shared_blocks == 2   # 8 tok / bs 4
        # the shared prompt means shared K/V: identical decode streams
        assert s.tokens == leader.tokens
    assert fleet.prefill_skips == 2
    assert sched.pool.num_free == sched.pool.num_usable
    sched.pool.check()


# ---------------------------------------------------------------------------
# consensus stop + mid-flight cancellation

def _consensus_fleet(n_groups=3, group_size=3, t=10, *, lam_sample=2.0,
                     consensus=None, paged=True, chunk_tokens=None,
                     prompt_len=1, n_slots=4, burn_in=2, extra_solo=0):
    n = n_groups * group_size
    bank = _bank(n + extra_solo, t, seed=6)
    # every sample of a group votes its group id: unanimous consensus
    answers = np.repeat(np.arange(n_groups), group_size)
    if extra_solo:
        answers = np.concatenate([answers, np.zeros(extra_solo, np.int64)])
    model = replay_model(bank, prompt_len=prompt_len, answers=answers)
    params = replay_params(bank, answers=answers)
    pc, theta = _probe(1.5, smooth_window=2)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=lam_sample,
                      burn_in=burn_in)
    sched = OrcaScheduler(model, params, pc, theta, cfg, n_slots=n_slots,
                          paged=paged, block_size=4,
                          chunk_tokens=chunk_tokens, consensus=consensus)
    reqs = _replay_reqs(n, [t] * n, group_size=group_size,
                        prompt_len=prompt_len)
    for i in range(extra_solo):
        reqs.append(make_request(np.full((prompt_len,), n + i, np.int64),
                                 max_new_tokens=t))
    return sched, reqs


def test_consensus_cancels_siblings_and_frees_pages():
    sched, reqs = _consensus_fleet(consensus=0.8)
    done, fleet = sched.run(reqs)
    for g in sched.groups:
        assert g.decided and g.consensus_answer == g.group_id
        assert g.consensus_index == 2          # fires right after burn-in
        assert g.consensus_agreement == pytest.approx(1.0)
        for r in g.requests:
            assert r.state is RequestState.CANCELLED and r.done
            assert r.stop_step == -1
            assert r.completed_step == g.consensus_step
            assert len(r.scores) == 3          # unspent budget returned
    assert fleet.samples_cancelled == 9
    assert fleet.consensus_groups == 3
    assert fleet.consensus_steps == pytest.approx(2.0)
    assert fleet.cancel_freed_blocks > 0
    # group savings COUNT the cancelled samples' unspent budget:
    # group_savings is the TOTAL unspent reasoning steps the fleet got back
    # (3 groups x (3 samples x 10 budget - 9 spent) = 63); the per-group
    # mean fraction lives in group_savings_mean
    assert fleet.group_savings == pytest.approx(3 * (3 * 10 - 9))
    assert fleet.group_savings_mean == pytest.approx(1.0 - 3 / 10)
    assert sched.pool.num_free == sched.pool.num_usable
    sched.pool.check()


def test_consensus_off_groups_run_to_their_own_stops():
    sched, reqs = _consensus_fleet(consensus=None)
    done, fleet = sched.run(reqs)
    assert fleet.samples_cancelled == 0 and fleet.consensus_groups == 0
    assert all(r.state is RequestState.FINISHED for r in done)
    assert sched.pool.num_free == sched.pool.num_usable


def test_cancelled_samples_excluded_from_latency_tails():
    sched, reqs = _consensus_fleet(consensus=0.8, extra_solo=2)
    done, fleet = sched.run(reqs)
    kept = [r for r in done if r.state is not RequestState.CANCELLED]
    assert len(kept) == 2
    ttft = np.array([r.ttft_s for r in kept if r.ttft_s >= 0]) * 1e3
    assert fleet.ttft_ms_p50 == pytest.approx(float(np.percentile(ttft, 50)))
    assert fleet.ttft_ms_p99 == pytest.approx(float(np.percentile(ttft, 99)))


def test_cancel_mid_prefill_leaves_pool_and_slot_clean():
    """Chunked prefill staggers the gang (sample spreading): the consensus
    fires while the LAST sibling is still mid-prefill — cancelling it must
    drop the parked row, its deferred donor plan and its pages without it
    ever decoding a token."""
    sched, reqs = _consensus_fleet(consensus=GroupCalibrator(
        min_votes=2, burn_in=0, lam=0.5), n_groups=1, prompt_len=24,
        chunk_tokens=4, burn_in=0, extra_solo=1)
    done, fleet = sched.run(reqs)
    grp = sched.groups[0]
    assert grp.decided
    last = max(grp.requests, key=lambda r: r.sample_idx)
    assert last.state is RequestState.CANCELLED
    assert last.prefill_progress < last.prompt_len   # cancelled MID-prefill
    assert len(last.tokens) == 0
    assert fleet.cancel_freed_blocks > 0
    # the freed slot and pages are genuinely reusable: the solo request
    # admitted after the gang still runs to completion
    solo = done[-1]
    assert solo.group_id is None
    assert solo.state is RequestState.FINISHED and len(solo.tokens) == 10
    assert sched.pool.num_free == sched.pool.num_usable
    sched.pool.check()
    # the cancelled slot's engine row is parked (frozen no-op compute)
    assert bool(sched._engine.st.stopped[last.slot])


# ---------------------------------------------------------------------------
# served == offline: the consensus decision sequence is the calibrated one

def test_served_consensus_matches_offline_trace():
    """The scheduler's per-step decide() replays ``consensus_trace`` +
    ``consensus_stop_times`` bit-for-bit: same fire index, same answer —
    including groups that never fire and samples frozen by budget."""
    n_groups, gs, t = 4, 3, 12
    n = n_groups * gs
    bank = _bank(n, t, seed=12)
    # mixed agreement: groups 0/2 unanimous, group 1 split 2-1, group 3
    # fully split (can never clear a 0.6 threshold)
    answers = np.repeat(np.arange(n_groups), gs)
    answers[5] = 90
    answers[9:12] = [91, 92, 93]
    lengths = np.array([12, 9, 12, 12, 12, 7, 10, 12, 12, 12, 12, 12])
    model = replay_model(bank, answers=answers)
    params = replay_params(bank, answers=answers)
    pc, theta = _probe(0.8, smooth_window=2)
    lam_g, burn = 0.6, 2
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=2.0,
                      burn_in=burn)
    # offline scores: the ungrouped serve of the same fleet (per-slot score
    # invariance makes these THE deployed-procedure scores)
    ref = OrcaScheduler(model, params, pc, theta, cfg, n_slots=4,
                        paged=True, block_size=4)
    base, _ = ref.run(_replay_reqs(n, lengths))
    sc = np.zeros((n, t))
    for i, r in enumerate(base):
        sc[i, :len(r.scores)] = r.scores
    an = np.repeat(answers[:, None], t, axis=1)

    sched = OrcaScheduler(model, params, pc, theta, cfg, n_slots=4,
                          paged=True, block_size=4,
                          consensus=GroupCalibrator(min_votes=2,
                                                    burn_in=burn,
                                                    lam=lam_g))
    done, fleet = sched.run(_replay_reqs(n, lengths, group_size=gs))
    fired = 0
    for g in sched.groups:
        rows = slice(g.group_id * gs, (g.group_id + 1) * gs)
        ans_t, agr_t = S.consensus_trace(sc[rows], an[rows], lengths[rows])
        tau = int(S.consensus_stop_times(agr_t, [lam_g], burn_in=burn)[0])
        if tau < int(lengths[rows].max()):
            assert g.decided and g.consensus_index == tau
            assert g.consensus_answer == int(ans_t[tau])
            fired += 1
        else:
            assert not g.decided
    assert 0 < fired < n_groups          # both outcomes exercised
    assert sched.pool.num_free == sched.pool.num_usable


# ---------------------------------------------------------------------------
# cancellation fuzz: group_size x budgets x policy x paged/dense

def _fuzz_round(group_size, n_slots, policy, paged, consensus_on, seed):
    n, t = 12 - (12 % max(group_size, 1)), 10
    bank = _bank(n, t, seed=seed)
    answers = (np.arange(n) // group_size if group_size else None)
    rs = np.random.RandomState(seed)
    lengths = rs.choice([6, 8, 10], size=n)
    model = replay_model(bank, answers=answers)
    params = replay_params(bank, answers=answers)
    pc, theta = _probe(1.2, smooth_window=2)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=0.65,
                      burn_in=1)
    consensus = 0.8 if (consensus_on and group_size >= 2) else None
    sched = OrcaScheduler(model, params, pc, theta, cfg, n_slots=n_slots,
                          paged=paged, block_size=4, policy=policy,
                          consensus=consensus)
    reqs = _replay_reqs(n, lengths, group_size=group_size or None)
    for i, r in enumerate(reqs):
        r.priority = i % 2
    done, fleet = sched.run(reqs)
    # every request terminal; cancelled ones only from decided groups
    assert all(r.done for r in done)
    for g in sched.groups:
        if g.n_cancelled:
            assert g.decided
        steps = {r.admitted_step for r in g.requests}
        assert len(steps) == 1                    # gang stayed atomic
    # no double slot occupancy across overlapping lifetimes — a preempted
    # request vacates its slot while SWAPPED, so its last residency starts
    # at restored_step, not admitted_step (step-level double ownership is
    # owned by tests/test_preemption.py + pool.check)
    def _resident_from(r):
        return r.restored_step if r.n_preempted else r.admitted_step
    for a, b in itertools.combinations(done, 2):
        # slot -1: cancelled while SWAPPED, it ended owning no slot
        if a.slot == b.slot >= 0:
            assert (a.completed_step <= _resident_from(b)
                    or b.completed_step <= _resident_from(a))
    if paged:
        # every page came home: refcounts hit 0, nothing leaked or doubled
        assert sched.pool.num_free == sched.pool.num_usable
        assert fleet.peak_blocks_in_use <= sched.pool.num_usable
        sched.pool.check()
    return done


@pytest.mark.parametrize("policy", ["fifo", "priority", "ttft"])
@pytest.mark.parametrize("group_size,paged", [(1, True), (2, False),
                                              (3, True), (4, True)])
def test_cancellation_invariants_pinned(policy, group_size, paged):
    _fuzz_round(group_size, max(4, group_size), policy, paged,
                consensus_on=True, seed=group_size)


@settings(max_examples=12, deadline=None)
@given(group_size=st.integers(1, 4), slot_pad=st.integers(0, 2),
       policy=st.sampled_from(["fifo", "priority", "ttft"]),
       paged=st.booleans(), consensus_on=st.booleans(),
       seed=st.integers(0, 5))
def test_cancellation_fuzz(group_size, slot_pad, policy, paged,
                           consensus_on, seed):
    done = _fuzz_round(group_size, group_size + slot_pad + 1, policy, paged,
                       consensus_on, seed)
    if group_size == 1 or not consensus_on:
        # inert layer: bit-equal to the ungrouped oracle
        oracle = _fuzz_round(0, group_size + slot_pad + 1, policy, paged,
                             consensus_on=False, seed=seed)
        assert [r.stop_step for r in done] == [r.stop_step for r in oracle]


# ---------------------------------------------------------------------------
# api facade end-to-end

def test_api_serve_requests_expands_groups():
    ts = generate(TrajectoryDistribution("facade", d_phi=D, t_min=8,
                                         t_max=12), 30, seed=2)
    calib = orca.fit(ts.subset(np.arange(15)), mode="consistent",
                     method="static", n_components=8, smooth_window=2,
                     epochs=40)
    fleet_ts = make_group_fleet(ts.subset(np.arange(15, 30)), 3, seed=0)
    sched = orca.engine(fleet_ts.model, fleet_ts.params, calib, n_slots=4,
                        lam=2.0, tokens_per_step=1, max_new_tokens=10,
                        group_size=3, consensus=0.8)
    prompts = np.stack([np.asarray(r.inputs["tokens"][0])
                        for r in fleet_ts.requests[::3]])
    done, fleet = orca.serve_requests(sched, prompts)
    assert len(done) == 3 * len(prompts)
    assert {r.group_id for r in done} == set(range(len(prompts)))
    assert all(r.done for r in done)
