"""Serving engine tests: pool invariants, scheduler policy, e2e parity.

Three layers, tested at three granularities:

- :class:`~deeplearning_mpi_tpu.serving.kv_pool.PagedKVPool` is pure
  host-side accounting, so it gets exhaustive treatment (alloc/free storms
  with ``check()`` after every operation).
- :class:`~deeplearning_mpi_tpu.serving.scheduler.Scheduler` policies
  (bounded queue, length admission, deadlines, FCFS, oldest-first
  eviction) run against a fake clock and a synthetic trace — every shed
  reason is produced deterministically.
- :class:`~deeplearning_mpi_tpu.serving.engine.ServingEngine` is pinned to
  the offline path: 8 staggered requests with ragged prompt lengths
  through the continuous-batching engine must produce BIT-IDENTICAL greedy
  outputs to per-request offline ``models.generate.generate`` — with
  mid-run slot reuse (a finished sequence's KV blocks reclaimed and handed
  to a later admission) exercised and asserted, because recycled-block
  correctness is exactly what the scratch-block and causal-masking design
  claims.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
from deeplearning_mpi_tpu.models.generate import generate
from deeplearning_mpi_tpu.models.transformer import (
    draft_config,
    truncate_lm_params,
)
from deeplearning_mpi_tpu.serving import (
    SCRATCH_BLOCK,
    DisaggregatedEngine,
    EngineConfig,
    PagedKVPool,
    RadixPrefixCache,
    Request,
    RequestState,
    Scheduler,
    ServingEngine,
)
from deeplearning_mpi_tpu.telemetry import MetricsRegistry


class FakeClock:
    """Deterministic injectable clock (the engine/scheduler take any
    zero-arg callable returning seconds)."""

    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float = 1.0) -> None:
        self.t += dt


def _req(rid, prompt_len, max_new=4, arrival=0.0, deadline=None):
    return Request(
        rid=rid,
        prompt=np.arange(1, prompt_len + 1, dtype=np.int32),
        max_new_tokens=max_new,
        arrival=arrival,
        deadline=deadline,
    )


class TestPagedKVPool:
    def test_constructor_validation(self):
        with pytest.raises(ValueError):
            PagedKVPool(1, 4)  # scratch only, nothing allocatable
        with pytest.raises(ValueError):
            PagedKVPool(8, 0)

    def test_capacity_excludes_scratch(self):
        pool = PagedKVPool(8, 4)
        assert pool.capacity == 7
        assert pool.available == 7
        assert pool.in_use == 0

    def test_blocks_for(self):
        pool = PagedKVPool(8, 4)
        assert [pool.blocks_for(n) for n in (1, 4, 5, 8, 9)] == [1, 1, 2, 2, 3]

    def test_alloc_is_deterministic_lowest_first_and_skips_scratch(self):
        pool = PagedKVPool(8, 4)
        assert pool.alloc(3) == [1, 2, 3]
        assert SCRATCH_BLOCK not in pool.alloc(4)
        pool.check()

    def test_alloc_all_or_nothing(self):
        pool = PagedKVPool(5, 4)  # capacity 4
        got = pool.alloc(3)
        assert got is not None
        before = pool.available
        assert pool.alloc(2) is None  # only 1 free: no partial reservation
        assert pool.available == before
        pool.check()

    def test_free_returns_blocks_for_reuse(self):
        pool = PagedKVPool(5, 4)
        a = pool.alloc(4)
        assert pool.alloc(1) is None
        pool.free(a[:2])
        assert pool.available == 2
        b = pool.alloc(2)
        assert set(b) == set(a[:2])  # freed blocks recirculate
        pool.check()

    def test_double_free_and_bogus_free_raise(self):
        pool = PagedKVPool(5, 4)
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(ValueError):
            pool.free(a)  # double free
        with pytest.raises(ValueError):
            pool.free([SCRATCH_BLOCK])  # scratch never allocatable
        with pytest.raises(ValueError):
            pool.free([99])  # out of range

    def test_alloc_free_storm_preserves_invariants(self):
        """Randomized churn — the invariant check runs after EVERY op, and
        the final drain must restore full capacity with matching lifetime
        counters (no leaked or duplicated blocks)."""
        rng = np.random.default_rng(0)
        pool = PagedKVPool(17, 4)
        held = []
        for _ in range(500):
            if held and rng.random() < 0.45:
                blocks = held.pop(rng.integers(len(held)))
                pool.free(blocks)
            else:
                got = pool.alloc(int(rng.integers(1, 5)))
                if got is not None:
                    held.append(got)
            pool.check()
            assert pool.available + pool.in_use == pool.capacity
        for blocks in held:
            pool.free(blocks)
        pool.check()
        assert pool.available == pool.capacity
        assert pool.total_allocated == pool.total_freed > 0


class TestScheduler:
    def _sched(self, *, num_blocks=9, block_size=4, max_slots=2,
               max_seq_len=32, max_queue=64):
        pool = PagedKVPool(num_blocks, block_size)
        return Scheduler(pool, max_slots=max_slots, max_seq_len=max_seq_len,
                         max_queue=max_queue), pool

    def test_submit_sheds_over_length_requests(self):
        sched, _ = self._sched(max_seq_len=16)
        req = _req(0, prompt_len=14, max_new=4)  # 18 > 16: can never finish
        assert not sched.submit(req)
        assert req.state is RequestState.SHED
        assert req.shed_reason == "too_long"
        assert sched.queue_depth() == 0

    def test_submit_sheds_on_full_queue(self):
        sched, _ = self._sched(max_queue=2)
        assert sched.submit(_req(0, 4))
        assert sched.submit(_req(1, 4))
        late = _req(2, 4)
        assert not sched.submit(late)
        assert late.shed_reason == "queue_full"
        assert sched.shed_count == 1

    def test_shed_expired_drops_only_past_deadline(self):
        sched, _ = self._sched()
        expired = _req(0, 4, arrival=0.0, deadline=5.0)
        alive = _req(1, 4, arrival=0.0, deadline=50.0)
        eternal = _req(2, 4, arrival=0.0, deadline=None)
        for r in (expired, alive, eternal):
            assert sched.submit(r)
        shed = sched.shed_expired(now=10.0)
        assert shed == [expired]
        assert expired.shed_reason == "deadline"
        assert sched.queue_depth() == 2
        assert alive.state is RequestState.QUEUED

    def test_admit_fcfs_allocates_prompt_blocks(self):
        sched, pool = self._sched(max_slots=2)
        a, b, c = _req(0, 5, arrival=0.0), _req(1, 3, arrival=1.0), \
            _req(2, 3, arrival=2.0)
        for r in (a, b, c):
            assert sched.submit(r)
        admitted = sched.admit(now=3.0)
        assert admitted == [a, b]  # arrival order, c waits for a slot
        assert a.slot == 0 and b.slot == 1
        assert len(a.blocks) == pool.blocks_for(5) == 2
        assert len(b.blocks) == 1
        assert a.state is RequestState.PREFILL and a.t_admitted == 3.0
        assert sched.queue_depth() == 1
        pool.check()

    def test_admit_head_of_line_blocks_on_kv_pressure(self):
        """FCFS means a big head request under KV pressure holds the line —
        a later small request is NOT admitted around it (skipping ahead
        would starve long prompts forever)."""
        sched, pool = self._sched(num_blocks=4, block_size=4, max_slots=2,
                                  max_seq_len=64)
        big = _req(0, 15, max_new=1, arrival=0.0)    # needs 4 > capacity 3
        small = _req(1, 3, max_new=1, arrival=1.0)   # would fit
        assert sched.submit(big) and sched.submit(small)
        assert sched.admit(now=2.0) == []
        assert sched.queue_depth() == 2
        assert pool.in_use == 0

    def test_grow_extends_by_one_block(self):
        sched, pool = self._sched()
        req = _req(0, 4)
        sched.submit(req)
        sched.admit(now=0.0)
        held = len(req.blocks)
        assert sched.grow(req)
        assert len(req.blocks) == held + 1
        pool.check()

    def test_grow_evicts_oldest_under_oom(self):
        sched, pool = self._sched(num_blocks=5, block_size=4)  # capacity 4
        old = _req(0, 8, arrival=0.0)    # 2 blocks
        young = _req(1, 8, arrival=1.0)  # 2 blocks — pool now full
        for r in (old, young):
            sched.submit(r)
        sched.admit(now=2.0)
        assert pool.available == 0
        assert sched.grow(young)  # evicts `old`, not the requester
        assert old.state is RequestState.SHED
        assert old.shed_reason == "evicted"
        assert sched.slots[old.slot if old.slot is not None else 0] is not old
        assert len(young.blocks) == 3
        assert sched.evicted_count == 1
        pool.check()

    def test_grow_self_evicts_when_requester_is_oldest(self):
        sched, pool = self._sched(num_blocks=5, block_size=4, max_slots=1)
        req = _req(0, 16, arrival=0.0)  # 4 blocks: the whole pool
        sched.submit(req)
        sched.admit(now=0.0)
        assert pool.available == 0
        assert not sched.grow(req)  # nothing older to evict: self-shed
        assert req.state is RequestState.SHED
        assert req.shed_reason == "evicted"
        assert sched.idle()
        pool.check()

    def test_shrink_returns_exact_tail_blocks(self):
        """Speculative rollback contract: ``shrink(req, keep)`` frees and
        returns EXACTLY the tail beyond ``keep`` — not a recount, not a
        fresh allocation's worth — so the engine's rolled-back-blocks
        counter is an identity, not an estimate."""
        sched, pool = self._sched()
        req = _req(0, 4)
        sched.submit(req)
        sched.admit(now=0.0)
        assert sched.grow(req) and sched.grow(req)
        held = list(req.blocks)
        avail = pool.available
        freed = sched.shrink(req, 1)
        assert freed == held[1:]
        assert req.blocks == held[:1]
        assert pool.available == avail + 2
        assert sched.shrink(req, 1) == []  # nothing past keep: no-op
        pool.check()

    def test_hold_decode_forms_larger_buckets(self):
        """Bucketed batch formation: with one sequence decoding and another
        prefilling, the scheduler holds decode (up to max_hold_steps) so
        the pair can step together at the next bucket."""
        sched, pool = self._sched(max_slots=2)
        sched.decode_buckets = (2,)
        sched.max_hold_steps = 2
        a, b = _req(0, 4, arrival=0.0), _req(1, 4, arrival=1.0)
        for r in (a, b):
            sched.submit(r)
        sched.admit(now=2.0)  # both PREFILL
        b.state = RequestState.PREFILL
        a.state = RequestState.DECODE
        assert sched.hold_decode(1)      # b's supply can reach bucket 2
        assert sched.hold_decode(1)
        assert not sched.hold_decode(1)  # max_hold_steps: stop starving a
        b.state = RequestState.DECODE
        assert not sched.hold_decode(2)  # bucket reached: no hold

    def test_hold_decode_without_buckets_is_inert(self):
        sched, _ = self._sched()
        assert not sched.hold_decode(1)

    def test_finish_releases_slot_and_blocks(self):
        sched, pool = self._sched()
        req = _req(0, 6)
        sched.submit(req)
        sched.admit(now=0.0)
        held = list(req.blocks)
        sched.finish(req, now=5.0)
        assert req.state is RequestState.FINISHED
        assert req.t_finished == 5.0
        assert req.blocks == held  # post-mortem record survives release
        assert pool.in_use == 0
        assert sched.idle()
        pool.check()

    def test_requeue_preserves_arrival_and_deadline(self):
        """Failover SLO contract, in-process half: a crashed-and-requeued
        request keeps its ORIGINAL arrival/deadline — recovery must never
        mint fresh budget — and a requeued request already past its
        deadline is shed on the next sweep, not served."""
        sched, pool = self._sched()
        req = _req(0, 6, arrival=1.0, deadline=9.0)
        sched.submit(req)
        sched.admit(now=2.0)
        sched.requeue(req)
        assert req.state is RequestState.QUEUED
        assert req.arrival == 1.0 and req.deadline == 9.0
        # requeue abandons block ownership; recovery's pool sweep reclaims.
        assert pool.reconcile([])["reclaimed"] > 0
        # still inside budget: survives the sweep...
        assert sched.shed_expired(now=8.0) == []
        # ...but a post-deadline recovery sheds it with the honest reason.
        assert sched.shed_expired(now=10.0) == [req]
        assert req.shed_reason == "deadline"
        pool.check()

    def test_cancel_queued_and_running(self):
        """Hedged-retry dedup: cancel() sheds the losing copy wherever it
        lives (queue or slot) with reason 'cancelled', and refuses
        double-cancel / cancel-after-finish."""
        sched, pool = self._sched(max_slots=1)
        running, queued = _req(0, 4, arrival=0.0), _req(1, 4, arrival=1.0)
        for r in (running, queued):
            sched.submit(r)
        sched.admit(now=2.0)  # one slot: `running` admitted, `queued` waits
        assert queued.state is RequestState.QUEUED
        assert sched.cancel(queued)
        assert queued.state is RequestState.SHED
        assert queued.shed_reason == "cancelled"
        assert sched.cancel(running)
        assert running.shed_reason == "cancelled"
        assert not sched.cancel(running)  # already shed: nothing to do
        assert pool.in_use == 0
        assert sched.idle()
        pool.check()

    def test_detach_vacates_slot_and_keeps_blocks(self):
        """The prefill half of a handoff: the request leaves its slot but
        KEEPS its KV blocks — block-table ownership is what moves between
        the disaggregated roles, not bytes."""
        sched, pool = self._sched(max_slots=1)
        req = _req(0, 5)
        sched.submit(req)
        sched.admit(now=0.0)
        blocks = list(req.blocks)
        sched.detach(req)
        assert req.slot is None
        assert req.blocks == blocks
        assert pool.in_use == len(blocks)  # nothing freed
        assert sched.slots_active() == 0
        with pytest.raises(ValueError, match="holds no slot"):
            sched.detach(req)  # double-detach

    def test_adopt_installs_into_free_slot_or_refuses(self):
        sched, pool = self._sched(max_slots=1)
        a, b = _req(0, 5), _req(1, 3, arrival=1.0)
        for r in (a, b):
            sched.submit(r)
        sched.admit(now=2.0)  # one slot: a admitted
        sched.detach(a)
        peer, _ = self._sched(max_slots=1)
        assert peer.adopt(a)
        assert a.slot == 0 and peer.running() == [a]
        with pytest.raises(ValueError, match="holds a slot"):
            peer.adopt(a)  # already slotted
        sched.admit(now=3.0)  # b takes the vacated prefill slot
        sched.detach(b)
        assert not peer.adopt(b)  # peer full: coordinator retries later
        assert b.slot is None
        pool.check()


# -- engine fixtures ---------------------------------------------------------

PROMPT_LENS = (5, 13, 3, 17, 1, 9, 2, 11)  # ragged on purpose
MAX_NEW = 5
ENGINE_CFG = EngineConfig(
    max_slots=3, block_size=4, num_blocks=32, max_blocks_per_seq=8,
    prefill_chunk=4,
)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig.tiny()
    model = TransformerLM(config=cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return cfg, model, params


def _offline_greedy(model, params, prompt, max_new):
    out = generate(
        model, params, jnp.asarray(prompt)[None], max_new_tokens=max_new,
        rng=jax.random.key(1), temperature=0.0,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.fixture(scope="module")
def parity_run(tiny_lm):
    """One staggered continuous-batching run shared by the e2e tests:
    8 ragged requests over 3 slots, arrivals spread across the run so
    later requests are admitted into slots (and KV blocks) that earlier
    finished requests just vacated."""
    cfg, model, params = tiny_lm
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, 255, size=n).astype(np.int32) for n in PROMPT_LENS
    ]
    offline = [_offline_greedy(model, params, p, MAX_NEW) for p in prompts]

    clock = FakeClock()
    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params, ENGINE_CFG, dtype=jnp.float32, clock=clock,
        registry=registry,
    )
    # Arrival schedule: 3 up front (fill every slot), the rest staggered so
    # they land mid-run as slots free.
    arrive_at_step = {0: [0, 1, 2], 2: [3, 4], 4: [5], 6: [6, 7]}
    reqs = {}
    step = 0
    while step in arrive_at_step or not engine.scheduler.idle():
        for i in arrive_at_step.get(step, []):
            reqs[i] = engine.submit(prompts[i], MAX_NEW)
        engine.step()
        clock.advance(1.0)
        step += 1
        assert step < 500, "engine did not drain"
    snapshot = registry.snapshot()  # before any other test mutates counters
    return {
        "engine": engine, "reqs": [reqs[i] for i in range(len(prompts))],
        "offline": offline, "snapshot": snapshot,
    }


class TestEngineParity:
    def test_all_requests_bit_identical_to_offline_greedy(self, parity_run):
        """The acceptance bar: every continuously-batched request produces
        exactly the tokens the offline per-request greedy decode produces —
        co-batched strangers, chunked prefill, paged KV, and slot churn
        must all be invisible to the output."""
        for req, expect in zip(parity_run["reqs"], parity_run["offline"]):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect, (
                f"rid={req.rid}: engine {req.generated} != offline {expect}"
            )

    def test_mid_run_slot_reuse_exercised(self, parity_run):
        """At least one later request must have been admitted after an
        earlier one finished AND hold recycled KV blocks — the run
        genuinely exercised reclaim+reassign, not just disjoint
        allocations."""
        reqs = parity_run["reqs"]
        reused = [
            (f.rid, g.rid)
            for f in reqs for g in reqs
            if f.t_finished is not None and g.t_admitted is not None
            and g.t_admitted >= f.t_finished
            and set(f.blocks) & set(g.blocks)
        ]
        assert reused, "no finished request's blocks were ever reassigned"

    def test_pool_drained_and_consistent(self, parity_run):
        pool = parity_run["engine"].pool
        pool.check()
        assert pool.in_use == 0
        assert pool.total_allocated == pool.total_freed > 0

    def test_serving_telemetry(self, parity_run):
        snap = parity_run["snapshot"]
        n = len(parity_run["reqs"])
        total_tokens = sum(len(r.generated) for r in parity_run["reqs"])
        assert snap["serve_requests_submitted"] == n
        assert snap["serve_requests_admitted"] == n
        assert snap["serve_requests_completed"] == n
        assert snap["serve_requests_shed"] == 0
        assert snap["serve_tokens_generated"] == total_tokens
        assert snap["serve_decode_steps"] > 0
        assert snap["serve_prefill_chunks"] >= n
        assert snap["serve_ttft_s_count"] == n
        assert snap["serve_tpot_s_count"] == n
        assert snap["serve_ttft_s_p50"] >= 0
        # Drained engine: the last step's gauges must read empty.
        assert snap["serve_queue_depth"] == 0
        assert snap["serve_slots_active"] == 0
        assert snap["serve_kv_blocks_in_use"] == 0

    def test_eos_stops_early(self, tiny_lm):
        """EOS retirement: pick the request's own second offline token as
        the EOS id — the engine must stop there, not at max_new_tokens."""
        cfg, model, params = tiny_lm
        prompt = np.arange(1, 8, dtype=np.int32)
        offline = _offline_greedy(model, params, prompt, MAX_NEW)
        eos = offline[1]
        expect = offline[: offline.index(eos) + 1]
        engine = ServingEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32, eos_id=eos,
        )
        req = engine.submit(prompt, MAX_NEW)
        engine.run_until_idle()
        assert req.state is RequestState.FINISHED
        assert req.generated == expect
        assert len(req.generated) < MAX_NEW

    def test_eviction_under_kv_pressure_preserves_survivors(self, tiny_lm):
        """A pool too small for every sequence's final length forces an
        eviction mid-run; the oldest request is shed with its partial
        output, and — the real claim — the survivors' outputs are STILL
        bit-identical to offline greedy: reclaiming a live sequence's
        blocks must not corrupt anyone else."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(1, 255, size=6).astype(np.int32) for _ in range(3)
        ]
        max_new = 8  # final length 14 -> 4 blocks/seq; 3*4 > capacity 9
        offline = [
            _offline_greedy(model, params, p, max_new) for p in prompts
        ]
        clock = FakeClock()
        engine = ServingEngine(
            cfg, params,
            EngineConfig(max_slots=3, block_size=4, num_blocks=10,
                         max_blocks_per_seq=8, prefill_chunk=4),
            dtype=jnp.float32, clock=clock,
        )
        reqs = []
        for p in prompts:  # distinct arrivals: eviction order deterministic
            reqs.append(engine.submit(p, max_new))
            clock.advance(1.0)
        engine.run_until_idle()

        evicted = [r for r in reqs if r.state is RequestState.SHED]
        survivors = [r for r in reqs if r.state is RequestState.FINISHED]
        assert [r.rid for r in evicted] == [reqs[0].rid]  # oldest-first
        assert evicted[0].shed_reason == "evicted"
        assert 0 < len(evicted[0].generated) < max_new  # partial output kept
        assert len(survivors) == 2
        for req, expect in zip(reqs[1:], offline[1:]):
            assert req.generated == expect
        engine.pool.check()
        assert engine.pool.in_use == 0

    def test_deadline_shed_before_admission(self, tiny_lm):
        cfg, _, params = tiny_lm
        clock = FakeClock()
        engine = ServingEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32, clock=clock,
        )
        req = engine.submit(np.arange(1, 5, dtype=np.int32), 4, deadline=2.0)
        clock.advance(10.0)  # client gave up before any step ran
        engine.step()
        assert req.state is RequestState.SHED
        assert req.shed_reason == "deadline"
        assert engine.scheduler.idle()


# -- speculative decoding ----------------------------------------------------


def _spec_engine(tiny_lm, *, draft_layers=1, spec_k=3, base_cfg=None, **kw):
    cfg, _, params = tiny_lm
    return ServingEngine(
        cfg, params,
        dataclasses.replace(base_cfg or ENGINE_CFG, spec_k=spec_k),
        dtype=jnp.float32,
        draft_config=draft_config(cfg, draft_layers),
        draft_params=truncate_lm_params(params, draft_layers),
        **kw,
    )


@pytest.fixture(scope="module")
def spec_parity_run(tiny_lm):
    """The staggered parity_run replayed through the SPECULATIVE engine
    (1-layer truncated draft, k=3): same arrival schedule, same slot churn
    and mid-run block recycling — now with draft proposals, batched verify
    steps, and rollback of rejected tails in the mix."""
    cfg, model, params = tiny_lm
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, 255, size=n).astype(np.int32) for n in PROMPT_LENS
    ]
    offline = [_offline_greedy(model, params, p, MAX_NEW) for p in prompts]
    clock = FakeClock()
    registry = MetricsRegistry()
    engine = _spec_engine(tiny_lm, clock=clock, registry=registry)
    arrive_at_step = {0: [0, 1, 2], 2: [3, 4], 4: [5], 6: [6, 7]}
    reqs = {}
    step = 0
    while step in arrive_at_step or not engine.scheduler.idle():
        for i in arrive_at_step.get(step, []):
            reqs[i] = engine.submit(prompts[i], MAX_NEW)
        engine.step()
        clock.advance(1.0)
        step += 1
        assert step < 500, "engine did not drain"
    return {
        "engine": engine, "reqs": [reqs[i] for i in range(len(prompts))],
        "offline": offline, "snapshot": registry.snapshot(),
    }


class TestSpeculativeDecoding:
    def test_staggered_parity_bit_identical(self, spec_parity_run):
        """THE speculative acceptance bar: exact-greedy-match acceptance
        means the draft can propose anything and every emitted stream is
        still bit-identical to offline greedy — under the same staggered
        arrivals and slot churn the plain-engine parity test uses."""
        for req, expect in zip(spec_parity_run["reqs"],
                               spec_parity_run["offline"]):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect, (
                f"rid={req.rid}: spec {req.generated} != offline {expect}"
            )

    def test_counters_reconcile(self, spec_parity_run):
        """Every proposed token is accounted for exactly once:
        proposed == accepted + rolled_back, with the verify/draft step
        counters live."""
        snap = spec_parity_run["snapshot"]
        prop = snap["spec_proposed_total"]
        assert prop > 0
        assert prop == snap["spec_accepted_total"] + snap["spec_rollback_total"]
        assert snap["spec_verify_steps"] > 0
        assert snap["spec_draft_steps"] > 0

    def test_pool_drained_after_rollbacks(self, spec_parity_run):
        pool = spec_parity_run["engine"].pool
        pool.check()
        assert pool.in_use == 0
        assert pool.total_allocated == pool.total_freed > 0

    def test_full_self_draft_accepts_everything(self, tiny_lm):
        """A draft identical to the target (all layers kept) agrees with
        every verify argmax, so acceptance is 100%, nothing rolls back,
        and the run takes strictly fewer decode steps than the plain
        engine on the same workload — the speedup mechanism, isolated."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(3)
        prompts = [
            rng.integers(1, 255, size=6).astype(np.int32) for _ in range(4)
        ]
        offline = [
            _offline_greedy(model, params, p, MAX_NEW) for p in prompts
        ]

        plain_reg = MetricsRegistry()
        plain = ServingEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32, registry=plain_reg,
        )
        for p in prompts:
            plain.submit(p, MAX_NEW)
        plain.run_until_idle()

        spec_reg = MetricsRegistry()
        engine = _spec_engine(
            tiny_lm, draft_layers=cfg.num_layers, registry=spec_reg,
        )
        reqs = [engine.submit(p, MAX_NEW) for p in prompts]
        engine.run_until_idle()

        for req, expect in zip(reqs, offline):
            assert req.generated == expect
        snap = spec_reg.snapshot()
        assert snap["spec_proposed_total"] > 0
        assert snap["spec_rollback_total"] == 0
        assert snap["spec_accepted_total"] == snap["spec_proposed_total"]
        assert (
            snap["serve_decode_steps"]
            < plain_reg.snapshot()["serve_decode_steps"]
        )

    def test_adversarial_draft_full_rollback_keeps_parity(self, tiny_lm):
        """Worst-case draft: proposals overridden (the documented test
        seam) with constant garbage. Throughput collapses; output must
        not change — and every rejected tail's blocks flow back through
        shrink, leaving the pool drained and the rolled-back-blocks
        counter consistent."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(13)
        prompts = [
            rng.integers(1, 255, size=n).astype(np.int32) for n in (5, 9, 3)
        ]
        offline = [
            _offline_greedy(model, params, p, MAX_NEW) for p in prompts
        ]
        registry = MetricsRegistry()
        engine = _spec_engine(tiny_lm, registry=registry)

        def garbage_propose(tables, lengths, last, n_prop, active):
            return np.zeros((len(last), 3), np.int32), 0

        engine._spec.propose = garbage_propose
        reqs = [engine.submit(p, MAX_NEW) for p in prompts]
        engine.run_until_idle()

        for req, expect in zip(reqs, offline):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect
        snap = registry.snapshot()
        prop = snap["spec_proposed_total"]
        assert prop > 0
        assert snap["spec_rollback_total"] > 0
        assert prop == snap["spec_accepted_total"] + snap["spec_rollback_total"]
        engine.pool.check()
        assert engine.pool.in_use == 0
        assert engine.pool.total_allocated == engine.pool.total_freed

    def test_spec_overflow_shed_reason(self, tiny_lm):
        """A verify batch that cannot cover its own KV growth self-sheds
        the oldest (the requester) under the dedicated
        ``serve_shed_total{reason="spec_overflow"}`` label — overflow is
        accounting, never a raise — and the survivor still matches
        offline greedy."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(5)
        long_p = rng.integers(1, 255, size=8).astype(np.int32)
        short_p = rng.integers(1, 255, size=7).astype(np.int32)
        offline_short = _offline_greedy(model, params, short_p, 5)
        clock = FakeClock()
        registry = MetricsRegistry()
        engine = _spec_engine(
            tiny_lm, clock=clock, registry=registry,
            base_cfg=EngineConfig(
                max_slots=2, block_size=4, num_blocks=5,
                max_blocks_per_seq=4, prefill_chunk=4,
            ),
        )
        a = engine.submit(long_p, 8)   # grows to 4 blocks: whole pool
        clock.advance(1.0)
        b = engine.submit(short_p, 5)  # 12 positions: 3 blocks
        engine.run_until_idle()

        assert a.state is RequestState.SHED
        assert a.shed_reason == "spec_overflow"
        assert b.state is RequestState.FINISHED
        assert b.generated == offline_short
        snap = registry.snapshot()
        assert snap['serve_shed_total{reason="spec_overflow"}'] == 1
        engine.pool.check()
        assert engine.pool.in_use == 0

    def test_rejects_spec_without_draft(self, tiny_lm):
        cfg, _, params = tiny_lm
        with pytest.raises(ValueError, match="draft"):
            ServingEngine(
                cfg, params, dataclasses.replace(ENGINE_CFG, spec_k=2),
                dtype=jnp.float32,
            )

    def test_rejects_vocab_mismatch_draft(self, tiny_lm):
        cfg, _, params = tiny_lm
        bad = dataclasses.replace(draft_config(cfg, 1), vocab_size=128)
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(
                cfg, params, dataclasses.replace(ENGINE_CFG, spec_k=2),
                dtype=jnp.float32, draft_config=bad,
                draft_params=truncate_lm_params(params, 1),
            )


class TestBucketedDecode:
    def test_held_steps_form_larger_batches_same_output(self, tiny_lm):
        """decode_buckets holds the decode phase while supply can reach a
        bigger bucket: the held-steps counter ticks, total decode steps do
        not increase vs the unbucketed parity run, and — the invariant
        that makes holding safe — every output is still bit-identical."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(1, 255, size=n).astype(np.int32)
            for n in PROMPT_LENS
        ]
        offline = [
            _offline_greedy(model, params, p, MAX_NEW) for p in prompts
        ]
        clock = FakeClock()
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg, params,
            dataclasses.replace(ENGINE_CFG, decode_buckets=(2, 3)),
            dtype=jnp.float32, clock=clock, registry=registry,
        )
        arrive_at_step = {0: [0, 1, 2], 2: [3, 4], 4: [5], 6: [6, 7]}
        reqs = {}
        step = 0
        while step in arrive_at_step or not engine.scheduler.idle():
            for i in arrive_at_step.get(step, []):
                reqs[i] = engine.submit(prompts[i], MAX_NEW)
            engine.step()
            clock.advance(1.0)
            step += 1
            assert step < 500, "engine did not drain"
        for i, expect in enumerate(offline):
            assert reqs[i].generated == expect
        assert registry.snapshot()["serve_decode_held_steps"] > 0


class TestEngineValidation:
    def test_rejects_moe_configs(self):
        import dataclasses

        cfg = dataclasses.replace(TransformerConfig.tiny(), moe_experts=4)
        with pytest.raises(NotImplementedError, match="capacity routing"):
            ServingEngine(cfg, {}, EngineConfig())

    def test_rejects_quantized_param_trees(self):
        fake = {"layer_0": {"attn": {"q_proj": {"scale": None}}}}
        with pytest.raises(NotImplementedError, match="raw f32"):
            ServingEngine(TransformerConfig.tiny(), fake, EngineConfig())

    def test_rejects_pool_smaller_than_one_sequence(self):
        fake = {"layer_0": {"attn": {"q_proj": {"kernel": None}}}}
        with pytest.raises(ValueError, match="pool capacity"):
            ServingEngine(
                TransformerConfig.tiny(), fake,
                EngineConfig(num_blocks=4, max_blocks_per_seq=8),
            )

    def test_engine_config_rejects_use_kernel(self):
        """The decode step has one schedule: the field is gone, not
        deprecated, so a stale config fails loudly."""
        with pytest.raises(TypeError, match="use_kernel"):
            EngineConfig(use_kernel=False)

    def test_rejects_nonpositive_max_new(self, tiny_lm):
        cfg, _, params = tiny_lm
        engine = ServingEngine(cfg, params, ENGINE_CFG, dtype=jnp.float32)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.arange(1, 4, dtype=np.int32), 0)

    def test_submit_arrival_override_pins_slo_budget(self, tiny_lm):
        """Failover SLO contract, cross-process half: a fleet supervisor
        re-dispatching a dead replica's request passes the ORIGINAL
        arrival, and an absolute deadline already in the past means the
        survivor sheds it as 'deadline' instead of quietly serving it on
        a brand-new budget."""
        cfg, _, params = tiny_lm
        clock = FakeClock(100.0)
        engine = ServingEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32, clock=clock
        )
        prompt = np.arange(1, 5, dtype=np.int32)
        fresh = engine.submit(prompt, 4)
        assert fresh.arrival == 100.0  # default: stamped now
        moved = engine.submit(prompt, 4, arrival=3.0, deadline=50.0)
        assert moved.arrival == 3.0 and moved.deadline == 50.0
        assert engine.scheduler.shed_expired(now=clock()) == [moved]
        assert moved.shed_reason == "deadline"
        assert fresh.state is RequestState.QUEUED  # no deadline: untouched


# -- disaggregated prefill/decode ---------------------------------------------

@pytest.fixture(scope="module")
def disagg_parity_run(tiny_lm):
    """The parity_run trace replayed through the disaggregated topology:
    same staggered arrivals, same engine config, but prefill and decode
    run in separate role engines bridged by the handoff queue over one
    shared KV pool."""
    cfg, model, params = tiny_lm
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, 255, size=n).astype(np.int32) for n in PROMPT_LENS
    ]
    offline = [_offline_greedy(model, params, p, MAX_NEW) for p in prompts]

    clock = FakeClock()
    registry = MetricsRegistry()
    engine = DisaggregatedEngine(
        cfg, params, ENGINE_CFG, dtype=jnp.float32, clock=clock,
        registry=registry,
    )
    arrive_at_step = {0: [0, 1, 2], 2: [3, 4], 4: [5], 6: [6, 7]}
    reqs = {}
    step = 0
    while step in arrive_at_step or not engine.idle():
        for i in arrive_at_step.get(step, []):
            reqs[i] = engine.submit(prompts[i], MAX_NEW)
        engine.step()
        clock.advance(1.0)
        step += 1
        assert step < 500, "disaggregated engine did not drain"
    snapshot = registry.snapshot()
    return {
        "engine": engine, "reqs": [reqs[i] for i in range(len(prompts))],
        "offline": offline, "snapshot": snapshot,
    }


class TestDisaggregatedServing:
    def test_streams_bit_identical_to_offline_greedy(self, disagg_parity_run):
        """The tentpole's correctness bar: splitting prefill and decode
        into separate engines (and moving sequences between them mid-
        flight) must be invisible in the tokens — same staggered trace,
        same outputs as offline greedy, hence as the colocated engine."""
        for req, expect in zip(
            disagg_parity_run["reqs"], disagg_parity_run["offline"]
        ):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect, (
                f"rid={req.rid}: disagg {req.generated} != offline {expect}"
            )

    def test_handoffs_actually_happened(self, disagg_parity_run):
        """Every request generating > 1 token must have crossed the
        handoff seam (prefill never decodes past the first token)."""
        snap = disagg_parity_run["snapshot"]
        crossing = sum(
            1 for r in disagg_parity_run["reqs"] if len(r.generated) > 1
        )
        assert snap["serve_handoffs_total"] == crossing > 0
        assert snap["serve_handoff_depth"] == 0  # drained

    def test_roles_stayed_in_their_lanes(self, disagg_parity_run):
        """Role-labeled telemetry proves the split: all prefill chunks on
        the prefill engine, all decode steps on the decode engine."""
        snap = disagg_parity_run["snapshot"]
        engine = disagg_parity_run["engine"]
        assert engine.prefill.role == "prefill"
        assert engine.decode.role == "decode"
        assert snap["serve_prefill_chunks"] >= len(disagg_parity_run["reqs"])
        assert snap["serve_decode_steps"] > 0
        # Per-role gauges exist and read drained.
        assert snap['serve_slots_active{role="prefill"}'] == 0
        assert snap['serve_slots_active{role="decode"}'] == 0

    def test_shared_pool_drained_and_consistent(self, disagg_parity_run):
        engine = disagg_parity_run["engine"]
        assert engine.prefill.pool is engine.decode.pool is engine.pool
        engine.pool.check()
        assert engine.pool.in_use == 0
        assert engine.pool.total_allocated == engine.pool.total_freed > 0

    def test_handoff_stall_and_crash_recovery(self, tiny_lm):
        """Chaos across the disaggregated seam: a handoff_stall wedges the
        queue (prefills pile up, decode drains), then a serve_crash inside
        prefill forces a cross-role recovery — and the books and the
        tokens both still balance."""
        from deeplearning_mpi_tpu.resilience import ChaosInjector

        cfg, model, params = tiny_lm
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(1, 255, size=n).astype(np.int32)
            for n in (5, 9, 3, 12)
        ]
        offline = [_offline_greedy(model, params, p, MAX_NEW) for p in prompts]
        registry = MetricsRegistry()
        chaos = ChaosInjector.from_spec(
            "handoff_stall@step:2,serve_crash@step:5", registry=registry
        )
        engine = DisaggregatedEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32,
            registry=registry, chaos=chaos,
        )
        reqs = [engine.submit(p, MAX_NEW) for p in prompts]
        engine.run_until_idle()
        for req, expect in zip(reqs, offline):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect
        snap = registry.snapshot()
        assert snap["fault_injected_total"] == 2
        assert snap["recovery_total"] == 2
        assert snap["serve_handoff_stalls_total"] == 1
        assert snap["serve_requeued_total"] > 0  # the crash requeued work
        assert chaos.balanced()
        engine.pool.check()
        assert engine.pool.in_use == 0

    def test_cancel_in_handoff_queue(self, tiny_lm):
        """A request cancelled while parked BETWEEN roles (prefill done,
        decode not yet adopted) must free its blocks and shed cleanly."""
        cfg, _, params = tiny_lm
        engine = DisaggregatedEngine(
            cfg, params, ENGINE_CFG, dtype=jnp.float32
        )
        req = engine.submit(np.arange(1, 6, dtype=np.int32), MAX_NEW)
        steps = 0
        while not engine.prefill.handoff:
            engine.prefill.step()  # prefill only: nothing drains the queue
            steps += 1
            assert steps < 100, "prompt never completed prefill"
        assert engine.cancel(req)
        assert req.state is RequestState.SHED
        assert req.shed_reason == "cancelled"
        assert engine.handoff_depth == 0
        assert engine.pool.in_use == 0
        engine.pool.check()
        assert not engine.cancel(req)  # already shed


# -- radix prefix cache -------------------------------------------------------

class TestPoolRefcounts:
    """The sharing layer under the prefix cache: refcounted free, frozen
    shared blocks (CoW), and multiplicity-aware crash reconciliation."""

    def test_share_requires_allocated_block(self):
        pool = PagedKVPool(8, 4)
        with pytest.raises(ValueError):
            pool.share([3])  # never allocated: sharing is never an alloc

    def test_shared_block_survives_first_free(self):
        pool = PagedKVPool(8, 4)
        (b,) = pool.alloc(1)
        pool.share([b])
        assert pool.refcount(b) == 2
        pool.free([b])  # one sharer drops out ...
        assert pool.refcount(b) == 1
        assert pool.in_use == 1  # ... pages still live for the other
        pool.free([b])  # last owner recycles
        assert pool.refcount(b) == 0
        assert pool.available == pool.capacity
        pool.check()

    def test_refcount_underflow_raises(self):
        pool = PagedKVPool(8, 4)
        torn = pool.alloc(1)
        pool._refcount[torn[0]] = 0  # corrupted books (double-freed sharer)
        with pytest.raises(ValueError, match="underflow"):
            pool.free(torn)

    def test_write_to_shared_block_requires_cow(self):
        pool = PagedKVPool(8, 4)
        shared = pool.alloc(1)
        pool.share(shared)
        with pytest.raises(ValueError, match="copy-on-write"):
            pool.record_fill(shared)
        pool.free(shared)  # back to sole ownership:
        pool.record_fill(shared)  # writes legal again
        pool.free(shared)
        pool.check()

    def test_reconcile_multiplicity_rebuilds_refcounts(self):
        """Recovery reports one entry per live REFERENCE (cache + each
        adopter), so a shared block must rebuild with every owner counted
        — and then drain with exactly that many frees."""
        pool = PagedKVPool(8, 4)
        a, b, leaked = pool.alloc(3)
        stats = pool.reconcile([a, a, b])
        assert stats == {"reclaimed": 1, "adopted": 0}
        assert pool.refcount(a) == 2
        assert pool.refcount(b) == 1
        assert pool.refcount(leaked) == 0  # reclaimed to the free list
        pool.check()
        pool.free([a, b])
        assert pool.in_use == 1  # a still held by its second owner
        pool.free([a])
        assert pool.in_use == 0
        pool.check()


class TestRadixPrefixCacheTrie:
    """Trie mechanics against a bare pool (no model): block-granularity
    matching, partial (CoW) adoption, upgrade/superspan tails, LRU
    eviction, flush."""

    BS = 4

    def _cache(self, num_blocks=32):
        pool = PagedKVPool(num_blocks, self.BS)
        return RadixPrefixCache(pool), pool

    def _complete(self, cache, pool, prompt, frozen):
        """Simulate a finished request: alloc its blocks, index the frozen
        span, then drop the request's own references (the cache keeps its
        shares alive)."""
        blocks = pool.alloc(pool.blocks_for(len(prompt)))
        cache.insert(prompt, blocks, frozen)
        pool.free(blocks)
        return blocks

    def test_miss_on_empty_cache(self):
        cache, _ = self._cache()
        assert cache.match(list(range(1, 10))) == (0, [], None)

    def test_full_block_adoption(self):
        cache, pool = self._cache()
        prompt = list(range(10, 23))  # 13 tokens: 3 full blocks + 1 row
        blocks = self._complete(cache, pool, prompt, frozen=12)
        fill, chain, partial = cache.match(prompt)
        assert (fill, chain, partial) == (12, blocks[:3], None)
        # The cache holds exactly one reference per indexed block.
        assert sorted(cache.referenced_blocks()) == sorted(blocks[:3])
        assert pool.in_use == 3  # the unfrozen 4th block was recycled

    def test_fill_caps_before_last_position(self):
        """An exact-prompt rematch must leave the final position
        unprefilled (the engine needs its logits for the first token) —
        the last matched block degrades to a partial CoW adoption."""
        cache, pool = self._cache()
        prompt = list(range(1, 13))  # 12 tokens, block-aligned
        blocks = self._complete(cache, pool, prompt, frozen=12)
        fill, chain, partial = cache.match(prompt)
        assert fill == 11 and chain == blocks[:2]
        assert partial == (blocks[2], 3)  # rows 8..10 of the third block

    def test_divergent_tail_partial_adoption(self):
        cache, pool = self._cache()
        a = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks = self._complete(cache, pool, a, frozen=8)
        b = [1, 2, 3, 4, 5, 6, 99, 98, 97, 96]  # shares 6 of 8
        fill, chain, partial = cache.match(b)
        assert fill == 6 and chain == blocks[:1]
        assert partial == (blocks[1], 2)  # copy, keep 2 rows, re-prefill rest

    def test_partial_upgrade_swaps_to_longer_tail(self):
        cache, pool = self._cache()
        base = [1, 2, 3, 4, 5, 6]
        self._complete(cache, pool, base, frozen=6)  # partial tail: 2 rows
        ext = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks2 = self._complete(cache, pool, ext, frozen=7)  # 3-row tail
        fill, _, partial = cache.match(ext)
        assert fill == 7  # the longer frozen tail won the node
        assert partial == (blocks2[1], 3)
        # The shorter tail's block lost its cache reference and recycled.
        assert pool.in_use == len(cache.referenced_blocks()) == 2
        pool.check()

    def test_superspan_incumbent_is_kept(self):
        cache, pool = self._cache()
        ext = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks1 = self._complete(cache, pool, ext, frozen=7)
        nodes_before = cache.num_nodes
        self._complete(cache, pool, [1, 2, 3, 4, 5, 6], frozen=6)
        assert cache.num_nodes == nodes_before  # subspan shares nothing
        fill, _, partial = cache.match(ext)
        assert fill == 7 and partial == (blocks1[1], 3)

    def test_evict_lru_sole_owner_only(self):
        cache, pool = self._cache()
        a = self._complete(cache, pool, [1, 2, 3, 4, 9], frozen=4)
        b = self._complete(cache, pool, [5, 6, 7, 8, 9], frozen=4)
        pool.share(a[:1])  # a live adopter pins A's block
        assert cache.evict(2) == 1  # only B (sole-owned) can be pruned
        assert cache.referenced_blocks() == a[:1]
        assert cache.match([5, 6, 7, 8, 9]) == (0, [], None)
        pool.free(a[:1])  # adopter finishes: A becomes evictable
        assert cache.evict(1) == 1
        assert pool.in_use == 0
        pool.check()

    def test_evict_prefers_least_recently_matched(self):
        cache, pool = self._cache()
        a = self._complete(cache, pool, [1, 2, 3, 4, 9], frozen=4)
        b = self._complete(cache, pool, [5, 6, 7, 8, 9], frozen=4)
        cache.match([1, 2, 3, 4, 9])  # touch A: B is now the LRU leaf
        assert cache.evict(1) == 1
        assert cache.referenced_blocks() == a[:1]
        assert b[0] not in cache.referenced_blocks()

    def test_flush_drops_everything(self):
        cache, pool = self._cache()
        self._complete(cache, pool, list(range(1, 14)), frozen=12)
        assert cache.flush() == 3
        assert pool.in_use == 0
        assert cache.num_nodes == 0
        assert cache.match(list(range(1, 14))) == (0, [], None)
        pool.check()


class TestTenantAdmission:
    def _sched(self, *, tenants, max_slots=2, num_blocks=33):
        pool = PagedKVPool(num_blocks, 4)
        registry = MetricsRegistry()
        sched = Scheduler(
            pool, max_slots=max_slots, max_seq_len=64, registry=registry,
            tenants=tenants,
        )
        return sched, registry

    def test_budget_sheds_over_committed_submit(self):
        """Budgets bound COMMITTED tokens (prompt + max_new over queued +
        running), so a tenant cannot exceed its worst-case footprint by
        racing submissions — and the budget frees as its requests leave."""
        sched, registry = self._sched(
            tenants={"burst": {"budget_tokens": 20}}
        )
        first = _req(0, 10, max_new=4)
        first.tenant = "burst"
        assert sched.submit(first)  # 14 committed <= 20
        second = _req(1, 10, max_new=4)
        second.tenant = "burst"
        assert not sched.submit(second)  # 28 > 20
        assert second.state is RequestState.SHED
        assert second.shed_reason == "tenant_budget"
        snap = registry.snapshot()
        assert snap['serve_shed_total{reason="tenant_budget"}'] == 1
        assert snap['serve_tenant_shed_total{tenant="burst"}'] == 1
        assert sched.tenant_tokens_in_flight() == {"burst": 14}
        # The shed request never entered the books; draining the first
        # frees the whole budget.
        sched.admit(0.0)
        sched.evict(first, reason="test_drain")
        assert sched.tenant_tokens_in_flight() == {}
        third = _req(2, 10, max_new=4)
        third.tenant = "burst"
        assert sched.submit(third)

    def test_unknown_and_zero_budget_tenants_are_unlimited(self):
        sched, _ = self._sched(
            tenants={"capped": {"budget_tokens": 10},
                     "free": {"budget_tokens": 0}}
        )
        for rid, tenant in enumerate(["free", "free", "nobody", "nobody"]):
            req = _req(rid, 10, max_new=4)
            req.tenant = tenant
            assert sched.submit(req), tenant

    def test_priority_orders_admission(self):
        """With a priority configured, the high-priority tenant admits
        first even when it arrived last; ties fall back to arrival."""
        sched, _ = self._sched(
            tenants={"vip": {"priority": 1.0}}, max_slots=1
        )
        late_default = _req(0, 8, arrival=0.0)
        vip = _req(1, 8, arrival=5.0)
        vip.tenant = "vip"
        assert sched.submit(late_default) and sched.submit(vip)
        admitted = sched.admit(now=6.0)
        assert [r.rid for r in admitted] == [1]  # vip took the only slot

    def test_no_priorities_preserves_fcfs(self):
        sched, _ = self._sched(
            tenants={"a": {"budget_tokens": 100}}, max_slots=2
        )
        r0, r1 = _req(0, 8, arrival=0.0), _req(1, 8, arrival=1.0)
        r1.tenant = "a"
        assert sched.submit(r0) and sched.submit(r1)
        assert [r.rid for r in sched.admit(now=2.0)] == [0, 1]


SHARED_PREAMBLE_LEN = 18  # 4 full blocks + 2 rows: adoption always CoWs


@pytest.fixture(scope="module")
def prefix_parity_run(tiny_lm):
    """Six prod requests sharing an 18-token preamble (plus distinct
    5-token tails) through an engine with the radix cache on, plus a
    two-submit burst tenant whose second submit must shed on budget."""
    cfg, model, params = tiny_lm
    rng = np.random.default_rng(21)
    preamble = rng.integers(1, 255, size=SHARED_PREAMBLE_LEN).astype(np.int32)
    prompts = [
        np.concatenate([preamble, rng.integers(1, 255, size=5).astype(np.int32)])
        for _ in range(8)
    ]
    offline = [_offline_greedy(model, params, p, MAX_NEW) for p in prompts]

    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params,
        dataclasses.replace(ENGINE_CFG, prefix_cache=True),
        dtype=jnp.float32, registry=registry,
        tenants={
            "prod": {"budget_tokens": 0, "priority": 1.0},
            # One burst request commits 23 + 4 = 27 tokens: budget 30
            # holds exactly one in flight.
            "burst": {"budget_tokens": 30, "priority": 0.0},
        },
    )
    reqs = [engine.submit(p, MAX_NEW, tenant="prod") for p in prompts[:6]]
    reqs.append(engine.submit(prompts[6], MAX_NEW, tenant="burst"))
    shed = engine.submit(prompts[7], MAX_NEW, tenant="burst")
    engine.run_until_idle()
    return {
        "engine": engine, "reqs": reqs, "shed": shed,
        "offline": offline, "snapshot": registry.snapshot(),
    }


class TestPrefixCacheServing:
    def test_streams_bit_identical_to_cold_oracle(self, prefix_parity_run):
        """The tentpole's correctness bar: adopted blocks, CoW copies, and
        skipped prefill must be invisible in the tokens — every stream
        matches the offline greedy decode of a COLD model."""
        for req, expect in zip(
            prefix_parity_run["reqs"], prefix_parity_run["offline"]
        ):
            assert req.state is RequestState.FINISHED
            assert req.generated == expect, (
                f"rid={req.rid}: cached {req.generated} != cold {expect}"
            )

    def test_cache_actually_worked(self, prefix_parity_run):
        snap = prefix_parity_run["snapshot"]
        assert snap["serve_prefix_hits_total"] > 0
        assert snap["serve_prefix_tokens_reused_total"] > 0
        # 18 % block_size != 0: every adoption crosses a CoW boundary.
        assert snap["serve_prefix_cow_copies_total"] > 0
        assert snap["serve_prefix_blocks"] > 0  # gauge: retained at drain

    def test_burst_tenant_shed_on_budget(self, prefix_parity_run):
        shed = prefix_parity_run["shed"]
        assert shed.state is RequestState.SHED
        assert shed.shed_reason == "tenant_budget"
        snap = prefix_parity_run["snapshot"]
        assert snap['serve_tenant_shed_total{tenant="burst"}'] == 1

    def test_refcount_books_balance_at_drain(self, prefix_parity_run):
        """LAST in this class (mutates the fixture): with every request
        gone, the pool's only references are the cache's; flush reconciles
        the books to exactly zero."""
        engine = prefix_parity_run["engine"]
        cache = engine.prefix_cache
        assert engine.pool.in_use == len(cache.referenced_blocks()) > 0
        cache.flush()
        assert engine.pool.in_use == 0
        assert engine.pool.total_allocated == engine.pool.total_freed > 0
        engine.pool.check()

    def test_cow_storm_with_eviction_parity(self, tiny_lm):
        """A pool far too small to retain the working set: admissions
        force LRU eviction of cached branches mid-run (and re-match after
        pruning). Token parity and the refcount books must survive the
        churn."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(5)
        preambles = [
            rng.integers(1, 255, size=10).astype(np.int32) for _ in range(3)
        ]
        prompts = [
            np.concatenate(
                [preambles[i % 3], rng.integers(1, 255, size=4).astype(np.int32)]
            )
            for i in range(9)
        ]
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg, params,
            dataclasses.replace(
                ENGINE_CFG, num_blocks=13, max_slots=2, prefix_cache=True
            ),
            dtype=jnp.float32, registry=registry,
        )
        reqs = [engine.submit(p, MAX_NEW) for p in prompts]
        engine.run_until_idle()
        snap = registry.snapshot()
        assert snap["serve_prefix_evictions_total"] > 0
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            assert req.generated == _offline_greedy(
                model, params, prompt, MAX_NEW
            )
        cache = engine.prefix_cache
        assert engine.pool.in_use == len(cache.referenced_blocks())
        cache.flush()
        assert engine.pool.in_use == 0
        engine.pool.check()


class TestPrefixCacheDisagg:
    def test_shared_prefix_crosses_handoff(self, tiny_lm):
        """Both roles consult ONE cache over the shared pool: a request
        admitted with adopted blocks prefills on the prefill engine, hands
        off, and decodes — bit-identical, with hits and handoffs > 0."""
        cfg, model, params = tiny_lm
        rng = np.random.default_rng(13)
        preamble = rng.integers(1, 255, size=SHARED_PREAMBLE_LEN).astype(
            np.int32
        )
        prompts = [
            np.concatenate(
                [preamble, rng.integers(1, 255, size=4).astype(np.int32)]
            )
            for _ in range(4)
        ]
        registry = MetricsRegistry()
        engine = DisaggregatedEngine(
            cfg, params,
            dataclasses.replace(ENGINE_CFG, prefix_cache=True),
            dtype=jnp.float32, registry=registry,
        )
        assert (
            engine.prefill.scheduler.prefix_cache
            is engine.decode.scheduler.prefix_cache
            is engine.prefix_cache
        )
        reqs = [engine.submit(p, MAX_NEW) for p in prompts]
        engine.run_until_idle()
        snap = registry.snapshot()
        assert snap["serve_prefix_hits_total"] > 0
        assert snap["serve_handoffs_total"] > 0
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            assert req.generated == _offline_greedy(
                model, params, prompt, MAX_NEW
            )
        assert engine.pool.in_use == len(engine.prefix_cache.referenced_blocks())
        engine.prefix_cache.flush()
        assert engine.pool.in_use == 0
        engine.pool.check()

    def test_weight_swap_flushes_cache(self, tiny_lm):
        """Cached KV computed under old params is bit-wrong under new ones
        — the params setter must flush before the next admission."""
        cfg, _, params = tiny_lm
        engine = DisaggregatedEngine(
            cfg, params,
            dataclasses.replace(ENGINE_CFG, prefix_cache=True),
            dtype=jnp.float32,
        )
        req = engine.submit(np.arange(1, 20, dtype=np.int32), MAX_NEW)
        engine.run_until_idle()
        assert req.state is RequestState.FINISHED
        assert engine.prefix_cache.num_blocks_cached > 0
        engine.params = params  # swap (same values: flush is what matters)
        assert engine.prefix_cache.num_blocks_cached == 0
        assert engine.pool.in_use == 0
        engine.pool.check()


# ---- the step from inside: host spans on the profiler's clock (PR 27) -----
_PHASE_LABELS = {
    "serve/step": {"step", "t"},
    "serve/admit": {"admitted", "queued"},
    "serve/prefill_launch": {"rid", "start", "n", "width", "topk"},
    "serve/first_token_fetch": {"rid"},
    "serve/grow": set(),
    "serve/decode_launch": {"rows", "table_rows", "width", "skipped", "topk"},
    "serve/token_fetch": set(),
    "serve/retire": {"finished"},
    "serve/gauges": set(),
}
_PROGRAMS = ["decode_step", "prefill_chunk", "verify_step"]
_PACKED = ["decode_step@packed", "prefill_chunk@packed"]  # at the smallest table shape
_WINDOW_CUT = "decode_step@window"  # a model with a window of 8: the table starts at the window's first block
_SCOPES = (
    "embed", "attn/qkv", "attn/kv_scatter", "attn/kv_gather", "attn/core",
    "attn/out", "mlp", "logits",
)


def _host_spans(trace_dir, prefixes=("serve/", "test/")):
    """(name, start s, duration s, labels) of every host event in the newest
    trace under ``trace_dir`` whose name starts with one of ``prefixes``, by
    start."""
    from jax.profiler import ProfileData

    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    labels = {k: v for k, v in e.stats}
                    spans.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9, labels))
    return sorted(spans, key=lambda s: s[1])


def _inside(child, parent):
    return parent[1] <= child[1] and child[1] + child[2] <= parent[1] + parent[2]


def _span_prompts():
    rng = np.random.default_rng(27)
    return [rng.integers(1, 255, size=n).astype(np.int32) for n in (3, 7, 2)]


def _serve_spans_run(engine, prompts):
    """r0 decodes alone, then r1 (two chunks) and r2 arrive one step apart:
    steps with a chunk and a decode batch, a first token and a decode batch,
    and decode alone."""
    import time as _time

    reqs = [engine.submit(prompts[0], MAX_NEW + 3)]
    engine.step()
    reqs.append(engine.submit(prompts[1], MAX_NEW))
    engine.step()
    reqs.append(engine.submit(prompts[2], MAX_NEW))
    steps = 2
    while not engine.scheduler.idle():
        engine.step()
        _time.sleep(0.0005)
        steps += 1
        assert steps < 200, "engine did not drain"
    return reqs


@pytest.fixture(scope="module")
def step_trace(tiny_lm, tmp_path_factory):
    """A warmed tiny engine on the real clock, stepped under a profiler
    session with Python call tracing off (what the benchmark runs)."""
    import time as _time

    cfg, _, params = tiny_lm
    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params, ENGINE_CFG, dtype=jnp.float32, clock=_time.monotonic,
        registry=registry,
    )
    programs = set(engine.warmup())
    engine.submit(np.arange(1, 6, dtype=np.int32), 2)
    engine.run_until_idle()  # the small programs around the engine's own
    compiles = registry.snapshot()["serve_compile_total"]
    trace_dir = tmp_path_factory.mktemp("serve_spans")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        t_mark = _time.monotonic()
        with jax.profiler.TraceAnnotation("test/mark"):
            pass
        reqs = _serve_spans_run(engine, _span_prompts())
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(trace_dir)
    steps = [s for s in spans if s[0] == "serve/step"]
    children = [
        [c for c in spans if c[0] not in ("serve/step", "test/mark") and _inside(c, st)]
        for st in steps
    ]
    return {
        "spans": spans, "steps": steps, "children": children, "reqs": reqs,
        "t_mark": t_mark, "compiles_before": compiles,
        "compiles_after": registry.snapshot()["serve_compile_total"],
        "inner": _host_spans(trace_dir, ("launch/", "fetch/")), "programs": programs,
        "fallbacks": registry.snapshot()["serve_program_fallbacks"], "trace_dir": trace_dir,
    }


class TestStepSpans:
    def test_every_phase_is_a_child_of_its_step(self, step_trace):
        """No ``serve/`` span outside a ``serve/step``, children of one step
        do not overlap, and a step is tiled by them (what is left is the
        step's own self time)."""
        spans, steps, children = (step_trace[k] for k in ("spans", "steps", "children"))
        assert len(steps) >= 6
        n_children = sum(len(c) for c in children)
        assert n_children == len([s for s in spans if s[0].startswith("serve/")]) - len(steps)
        for st, kids in zip(steps, children):
            assert kids[0][0] == "serve/admit" and kids[-1][0] == "serve/gauges"
            for a, b in zip(kids, kids[1:]):
                assert a[1] + a[2] <= b[1], (a, b)
            assert sum(k[2] for k in kids) <= st[2]

    def test_a_step_with_a_chunk_and_a_decode_batch_has_both_launches_and_both_fetches(self, step_trace):
        names = [[k[0] for k in kids] for kids in step_trace["children"]]
        assert names[0] == [  # r0's only chunk is its last: first token, then it decodes
            "serve/admit", "serve/prefill_launch", "serve/first_token_fetch", "serve/retire",
            "serve/grow", "serve/decode_launch", "serve/token_fetch", "serve/retire", "serve/gauges",
        ]
        assert names[1] == [  # r1's first of two chunks beside r0's decode
            "serve/admit", "serve/prefill_launch", "serve/grow", "serve/decode_launch",
            "serve/token_fetch", "serve/retire", "serve/gauges",
        ]
        assert names[-1] == [
            "serve/admit", "serve/grow", "serve/decode_launch", "serve/token_fetch",
            "serve/retire", "serve/gauges",
        ]
        assert "serve/cow" not in {n for step in names for n in step}  # no prefix cache, no copy

    def test_spans_carry_the_labels_of_the_table(self, step_trace):
        steps, children, reqs = (step_trace[k] for k in ("steps", "children", "reqs"))
        for st, kids in zip(steps, children):
            for name, _, _, labels in [st, *kids]:
                assert set(labels) == _PHASE_LABELS[name], (name, labels)
        assert [st[3]["step"] for st in steps] == list(range(steps[0][3]["step"], steps[0][3]["step"] + len(steps)))
        chunks = [k[3] for kids in children for k in kids if k[0] == "serve/prefill_launch"]
        by_rid = {r.rid: r for r in reqs}
        assert [(c["rid"], c["start"], c["n"]) for c in chunks] == [
            (reqs[0].rid, 0, 3), (reqs[1].rid, 0, 4), (reqs[1].rid, 4, 3), (reqs[2].rid, 0, 2),
        ]
        assert all(by_rid[c["rid"]].prompt_len >= c["start"] + c["n"] for c in chunks)
        admits = [kids[0][3] for kids in children]
        assert sum(a["admitted"] for a in admits) == 3 and all(a["queued"] == 0 for a in admits)
        launches = [k[3] for kids in children for k in kids if k[0] == "serve/decode_launch"]
        assert max(l["rows"] for l in launches) == 3 and {l["width"] for l in launches} <= {1, 2, 4, 8}
        assert all(l["rows"] <= l["table_rows"] <= 3 for l in launches)
        assert {l["table_rows"] for l in launches} == {1, 3}  # r0 alone; r1 and r2 reach decode in one step
        assert [c["width"] for c in chunks] == [1, 1, 2, 1]  # the chunk's reach, not the blocks held
        retired = sum(k[3]["finished"] for kids in children for k in kids if k[0] == "serve/retire")
        assert retired == 3

    def test_the_t_label_ties_the_engine_clock_to_the_trace_clock(self, step_trace):
        """``t`` minus the span's own start is the engine clock's reading at
        the profiler session's zero; an annotation stamped by hand in the
        same session gives the same offset to within 1 ms."""
        mark = next(s for s in step_trace["spans"] if s[0] == "test/mark")
        reference = step_trace["t_mark"] - mark[1]
        offsets = [st[3]["t"] - st[1] for st in step_trace["steps"]]
        assert max(abs(o - reference) for o in offsets) < 1e-3

    def test_t_admitted_maps_into_the_step_that_admitted_it(self, step_trace):
        """``Request.t_admitted`` is the step's ``now`` (its ``t`` label), read
        just before the spans open: mapped onto the trace's clock it lies
        within a millisecond before the ``serve/admit`` span that admitted the
        request, never after it, and in no other step."""
        steps, children, reqs = (step_trace[k] for k in ("steps", "children", "reqs"))
        offset = steps[0][3]["t"] - steps[0][1]
        admitting = [(st, kids[0]) for st, kids in zip(steps, children) if kids[0][3]["admitted"]]
        assert len(admitting) == len(reqs)
        for req, (st, admit) in zip(reqs, admitting):
            at = req.t_admitted - offset
            assert admit[1] - 1e-3 <= at <= admit[1] + admit[2]
            assert req.t_admitted == st[3]["t"]
            later = [s for s in steps if s[1] > st[1]]
            assert not later or at < later[0][1]

    def test_no_compile_after_warmup_under_the_profiler(self, step_trace):
        assert step_trace["compiles_after"] == step_trace["compiles_before"]

    def test_tokens_identical_with_annotations_stripped(self, step_trace, tiny_lm):
        """``trace.set_enabled(False)`` strips the host spans AND the scopes
        inside the programs (they are built with it off): same tokens, and
        ``serve_compile_total`` still flat after ``warmup()``."""
        from deeplearning_mpi_tpu.telemetry import trace

        cfg, model, params = tiny_lm
        old = trace.set_enabled(False)
        try:
            registry = MetricsRegistry()
            engine = ServingEngine(cfg, params, ENGINE_CFG, dtype=jnp.float32, registry=registry)
            engine.warmup()
            warmed = registry.snapshot()["serve_compile_total"]
            reqs = _serve_spans_run(engine, _span_prompts())
        finally:
            trace.set_enabled(old)
        assert registry.snapshot()["serve_compile_total"] == warmed
        assert registry.snapshot()["serve_program_fallbacks"] == 0
        assert [r.generated for r in reqs] == [r.generated for r in step_trace["reqs"]]
        for req, prompt in zip(reqs, _span_prompts()):
            assert req.generated == _offline_greedy(model, params, prompt, req.max_new_tokens)

    # -- inside the launch and the fetch (launch/, fetch/: serving/launch.py) --
    def test_every_launch_builds_then_hands_over_then_dispatches(self, step_trace):
        """Inside every decode and prefill launch, one after another:
        ``launch/prep``, ``launch/h2d`` (``bytes``: the arrays handed over),
        ``launch/dispatch`` (``program``: the warmed executable of the
        launch's table; ``fallback`` 0)."""
        spans, inner = step_trace["spans"], step_trace["inner"]
        launches = [s for s in spans if s[0] in ("serve/decode_launch", "serve/prefill_launch")]
        assert {s[0] for s in launches} == {"serve/decode_launch", "serve/prefill_launch"}
        assert all(any(_inside(e, s) for s in spans if s[0] != "serve/step") for e in inner)
        for launch in launches:
            kids = [e for e in inner if _inside(e, launch)]
            assert [k[0] for k in kids] == ["launch/prep", "launch/h2d", "launch/dispatch"], launch
            for a, b in zip(kids, kids[1:]):
                assert a[1] + a[2] <= b[1]
            prep, h2d, dispatch = (k[3] for k in kids)
            labels = launch[3]
            assert prep == {} and dispatch["fallback"] == 0 and dispatch["program"] in step_trace["programs"]
            if launch[0] == "serve/decode_launch":  # int32 tables, lengths and tokens, bool active
                rows, width = labels["table_rows"], labels["width"]
                assert h2d["bytes"] == 4 * rows * (width + 2) + rows
                assert dispatch["program"] in (f"serve_decode_step@{rows}x{width}", "serve_decode_step")
            else:  # int32 table, chunk, start and n_valid
                assert h2d["bytes"] == 4 * (labels["width"] + ENGINE_CFG.prefill_chunk + 2)
                assert dispatch["program"] in (f"serve_prefill_chunk@{labels['width']}", "serve_prefill_chunk")

    def test_every_fetch_waits_then_copies_back(self, step_trace):
        """Inside every token fetch: ``fetch/ready`` then ``fetch/d2h``, whose
        ``bytes`` is the int32 tokens of the launch's table rows (one for a
        first token)."""
        spans, inner = step_trace["spans"], step_trace["inner"]
        decode = [s for s in spans if s[0] == "serve/decode_launch"]
        fetches = [s for s in spans if s[0] in ("serve/token_fetch", "serve/first_token_fetch")]
        assert len([f for f in fetches if f[0] == "serve/token_fetch"]) == len(decode)
        assert len([f for f in fetches if f[0] == "serve/first_token_fetch"]) == len(step_trace["reqs"])
        rows = iter(s[3]["table_rows"] for s in decode)
        for fetch in fetches:
            kids = [e for e in inner if _inside(e, fetch)]
            assert [k[0] for k in kids] == ["fetch/ready", "fetch/d2h"], fetch
            assert kids[0][1] + kids[0][2] <= kids[1][1]
            assert kids[1][3]["bytes"] == 4 * (next(rows) if fetch[0] == "serve/token_fetch" else 1)

    def test_the_idle_reader_nests_the_engines_own_trace(self, step_trace):
        """``benchmark/readers/serve_idle.py`` on the same trace: every step,
        with the launch's three children and the fetch's two under them."""
        from benchmark.readers import serve_idle

        host = serve_idle.host_side(step_trace["trace_dir"])
        assert [st.start for st in host.steps] == pytest.approx([st[1] for st in step_trace["steps"]])
        assert host.has("launch/") and host.has("fetch/")
        children = {
            "serve/decode_launch": ["launch/prep", "launch/h2d", "launch/dispatch"],
            "serve/prefill_launch": ["launch/prep", "launch/h2d", "launch/dispatch"],
            "serve/token_fetch": ["fetch/ready", "fetch/d2h"],
            "serve/first_token_fetch": ["fetch/ready", "fetch/d2h"],
        }
        for st in host.steps:
            for path, node in st.walk():
                if len(path) == 2:  # a phase: the launches and the fetches hold the new spans, no other phase any
                    assert [c.name for c in node.children] == children.get(node.name, [])
                assert len(path) < 3 or node.children == []

    def test_a_program_off_its_executable_is_counted_and_labelled(self, tiny_lm, tmp_path):
        """``serve_program_fallbacks`` counts every call ``WarmProgram`` hands
        to its jit net, and that launch's ``launch/dispatch`` says so."""
        cfg, _, params = tiny_lm
        registry = MetricsRegistry()
        engine = ServingEngine(cfg, params, ENGINE_CFG, dtype=jnp.float32, registry=registry)
        engine.warmup()
        engine._prefill_fn.program = {}  # no executable for any table: every chunk takes the net
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            engine.submit(np.arange(1, 8, dtype=np.int32), 2)  # two chunks of 4
            engine.run_until_idle()
        finally:
            jax.profiler.stop_trace()
        snap = registry.snapshot()
        assert snap["serve_prefill_chunks"] == 2 == snap["serve_program_fallbacks"]
        dispatched = [(e[3]["program"], e[3]["fallback"]) for e in _host_spans(tmp_path, ("launch/dispatch",))]
        assert [d for d in dispatched if d[1]] == [("jit_prefill_chunk", 1)] * 2
        assert len(dispatched) > 2 and all(p.startswith("serve_decode_step") for p, f in dispatched if not f)

    def test_the_speculative_and_copy_on_write_launches_hold_the_same_children(self, tiny_lm, tmp_path):
        """A speculating engine with a prefix cache: every ``serve/cow``
        holds a transfer and a dispatch for the target's pools and again for
        the draft's; every ``serve/verify_launch`` and every propose step of
        ``serve/draft_launch`` prep, h2d and dispatch in order, each
        propose step's fetch inside the draft launch; every
        ``serve/verify_fetch`` ``fetch/ready`` then ``fetch/d2h``. The
        verify step and the draft's programs have one executable each, at
        the full table: their narrower widths run pre-traced through the jit
        net, so those dispatches say ``fallback`` 1 and are what
        ``serve_program_fallbacks`` counts; the tokens are the untraced
        engine's."""
        from deeplearning_mpi_tpu.telemetry import trace

        rng = np.random.default_rng(38)
        preamble = rng.integers(1, 255, size=SHARED_PREAMBLE_LEN).astype(np.int32)
        prompts = [np.concatenate([preamble, rng.integers(1, 255, size=3).astype(np.int32)]) for _ in range(3)]

        def serve(traced):
            registry = MetricsRegistry()
            engine = _spec_engine(
                tiny_lm, spec_k=2, base_cfg=dataclasses.replace(ENGINE_CFG, prefix_cache=True), registry=registry,
            )
            programs = set(engine.warmup())
            old = trace.set_enabled(traced)
            try:
                reqs = [engine.submit(prompts[0], MAX_NEW)]
                engine.run_until_idle()  # the preamble is cached from here on: the next two adopt it and copy on write
                reqs += [engine.submit(p, MAX_NEW) for p in prompts[1:]]
                engine.run_until_idle()
            finally:
                trace.set_enabled(old)
            return [r.generated for r in reqs], registry.snapshot(), programs

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            tokens, snap, programs = serve(True)
        finally:
            jax.profiler.stop_trace()
        assert tokens == serve(False)[0]
        spans, inner = _host_spans(tmp_path), _host_spans(tmp_path, ("launch/", "fetch/"))

        def kids(name):
            out = [[e for e in inner if _inside(e, s)] for s in spans if s[0] == name]
            assert out, name
            return out

        launch = ["launch/prep", "launch/h2d", "launch/dispatch"]
        for ks in kids("serve/cow"):  # two copies a copy-on-write (target, draft), both adopters in one phase
            assert [k[0] for k in ks] == ["launch/h2d", "launch/dispatch"] * 2 * (len(ks) // 4) and ks
        assert all([k[0] for k in ks] == launch for ks in kids("serve/verify_launch"))
        assert all([k[0] for k in ks] == ["fetch/ready", "fetch/d2h"] for ks in kids("serve/verify_fetch"))
        for ks in kids("serve/draft_launch"):  # the budget's own prep, then one launch and one fetch a propose step
            names = [k[0] for k in ks]
            assert names[0] == "launch/prep" and len(names) > 1
            assert names[1:] == (launch + ["fetch/ready", "fetch/d2h"]) * ((len(names) - 1) // 5)
        dispatched = [e[3] for e in inner if e[0] == "launch/dispatch"]
        assert {d["program"] for d in dispatched if not d["fallback"]} <= programs
        assert {d["program"] for d in dispatched if d["fallback"]} <= {"jit_verify_step", "jit_decode_step", "jit_prefill_chunk"}
        assert sum(d["fallback"] for d in dispatched) == snap["serve_program_fallbacks"] > 0

    @staticmethod
    def _program(engine, program):
        """One of the engine's three jitted programs and arguments (after
        ``params`` and ``kv``) of the shapes it is compiled for;
        ``<program>@packed`` at the smallest table the bucket functions emit."""
        e = engine.engine
        program, _, packed = program.partition("@")
        rows, width = {
            "": (e.max_slots, e.max_blocks_per_seq), "packed": engine._decode_shapes[0],
            "window": engine._decode_shapes[-1],
        }[packed]
        slots = jnp.zeros((rows,), jnp.int32)
        tables = jnp.zeros((rows, width), jnp.int32)
        live = jnp.zeros((rows,), bool)
        return {
            "decode_step": (engine._decode_jit, (tables, slots, slots, live)),
            "prefill_chunk": (engine._prefill_jit, (
                jnp.zeros((engine._widths[0] if packed else width,), jnp.int32),
                jnp.zeros((e.prefill_chunk,), jnp.int32), jnp.int32(0), jnp.int32(1),
            )),
            "verify_step": (engine._verify_jit, (
                tables, slots, jnp.zeros((e.max_slots, e.spec_k + 1), jnp.int32), slots, live,
            )),
        }[program]

    @pytest.mark.parametrize("program", [*_PROGRAMS, *_PACKED])
    def test_programs_are_named_and_scoped(self, tiny_lm, program):
        """The lowered module is ``jit_<program>`` (nothing ``_unknown``) and
        every operation's location carries one of the layer scopes."""
        engine = _spec_engine(tiny_lm)
        jitted, args = self._program(engine, program)
        program = program.partition("@")[0]
        text = jitted.lower(engine.params, engine._kv, *args).as_text(debug_info=True)
        assert f"module @jit_{program} " in text and "_unknown" not in text
        for scope in _SCOPES:
            assert f"jit({program})/{scope}/" in text, scope
        if program == "decode_step":  # the draft's, too
            draft = engine._spec._decode_jit.lower(
                engine._spec.params, engine._spec._kv, *args
            ).as_text()
            assert "module @jit_decode_step " in draft

    @pytest.mark.parametrize("program", _PROGRAMS)
    def test_every_program_runs_the_one_layer_body(self, tiny_lm, program):
        """Each program enters each of the six layer scopes exactly
        ``num_layers`` times, in the body's order: walking the traced
        program's equations in order, the scope changes
        qkv -> kv_scatter -> kv_gather -> core -> out -> mlp once a layer."""
        engine = _spec_engine(tiny_lm)
        jitted, args = self._program(engine, program)
        layer_scopes = [s for s in _SCOPES if s not in ("embed", "logits")]
        entered = []
        (call,) = jax.make_jaxpr(jitted)(engine.params, engine._kv, *args).jaxpr.eqns
        for eqn in call.params["jaxpr"].jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            scope = next((s for s in layer_scopes if stack.endswith(s)), None)
            if scope is not None and (not entered or entered[-1] != scope):
                entered.append(scope)
        assert entered == layer_scopes * engine.config.num_layers

    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "int8"])
    @pytest.mark.parametrize("program", [*_PROGRAMS, *_PACKED, _WINDOW_CUT])
    def test_page_gather_reads_the_pool_in_place(self, tiny_lm, program, kv_dtype):
        """No value of a per-layer pool's shape exists in any program, and
        every gather of KV pages (or their scales) takes a whole pool as its
        operand: ``pool[i][tables]`` would copy layer ``i``'s whole pool
        once per K and V per layer before gathering from the copy."""
        if program == _WINDOW_CUT:
            cfg, model, params = tiny_lm
            tiny_lm = (dataclasses.replace(cfg, attention_window=8), model, params)
        engine = _spec_engine(tiny_lm, base_cfg=dataclasses.replace(ENGINE_CFG, kv_dtype=kv_dtype))
        jitted, args = self._program(engine, program)
        if program == _WINDOW_CUT:  # 8 positions lie in at most 3 blocks of 4, of the 8 a sequence may hold
            assert engine._fwd.decode_window == 8 and args[0].shape == (ENGINE_CFG.max_slots, 3)
        pools = {buf.shape for buf in engine._kv}
        assert len(pools) == (2 if kv_dtype else 1)
        layer_slices = {shape[1:] for shape in pools}
        pages = {shape[2:] for shape in pools}  # [block_size, Hkv(, D)]

        def equations(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from equations(sub)

        closed = jax.make_jaxpr(jitted)(engine.params, engine._kv, *args)
        kv_gathers = 0
        for eqn in equations(closed.jaxpr):
            for out in eqn.outvars:
                assert getattr(out.aval, "shape", None) not in layer_slices, eqn
            if eqn.primitive.name == "gather":
                operand = eqn.invars[0].aval.shape
                if any(operand[-len(page):] == page for page in pages):
                    assert operand in pools, eqn
                    kv_gathers += 1
        assert kv_gathers == len(engine._kv) * engine.config.num_layers


# ---- the block table at the live rows x the live width (PR 31) -------------
# max_slots 4 and 8 blocks a sequence: row buckets (1, 2, 4), widths
# (1, 2, 4, 8), decode shapes 1, 2, 4 x (4, 8).
LIVE_CFG = EngineConfig(
    max_slots=4, block_size=4, num_blocks=64, max_blocks_per_seq=8,
    prefill_chunk=4, max_queue=16,
)


def _own(kv):
    """A copy of the pools that a donating program may consume."""
    return tuple(jnp.array(buf) for buf in kv)


def _pages(kv):
    """Every pool past the scratch block, which pad rows write to."""
    return [np.asarray(buf)[:, 1:] for buf in kv]


def _prefilled(engine, prompts, tables):
    """Pools holding ``prompts`` written whole through ``tables``, by the
    full-width prefill program."""
    e = engine.engine
    kv = _own(engine._kv)
    for prompt, table in zip(prompts, tables):
        for start in range(0, len(prompt), e.prefill_chunk):
            n = min(e.prefill_chunk, len(prompt) - start)
            chunk = np.zeros((e.prefill_chunk,), np.int32)
            chunk[:n] = prompt[start:start + n]
            kv, _ = engine._prefill_jit(
                engine.params, kv, jnp.asarray(table), jnp.asarray(chunk), jnp.int32(start), jnp.int32(n),
            )
    return kv


def _slot_batch(e, slots, prompts, held):
    """Host arrays of a decode step at the engine's ceilings: ``prompts[i]``
    plus one fed token in slot ``slots[i]``, holding blocks ``held[i]``."""
    tables = np.zeros((e.max_slots, e.max_blocks_per_seq), np.int32)
    lengths = np.zeros((e.max_slots,), np.int32)
    tokens = np.zeros((e.max_slots,), np.int32)
    active = np.zeros((e.max_slots,), bool)
    for slot, prompt, blocks in zip(slots, prompts, held):
        tables[slot, :len(blocks)] = blocks
        lengths[slot] = len(prompt) + 1
        tokens[slot] = 17 + slot
        active[slot] = True
    return tables, lengths, tokens, active


def _full_shape(engine):
    """The same engine held to the one table shape of its ceilings."""
    e = engine.engine
    engine._widths = (e.max_blocks_per_seq,)
    engine._decode_shapes = ((e.max_slots, e.max_blocks_per_seq),)
    return engine


def _recorded(engine):
    """Record the table shapes the engine hands its two programs."""
    seen = {"decode": set(), "prefill": set()}
    decode, prefill = engine._decode_fn, engine._prefill_fn

    def decode_fn(params, kv, tables, *rest):
        seen["decode"].add(tuple(tables.shape))
        return decode(params, kv, tables, *rest)

    def prefill_fn(params, kv, table, *rest):
        seen["prefill"].add(table.shape[0])
        return prefill(params, kv, table, *rest)

    engine._decode_fn, engine._prefill_fn = decode_fn, prefill_fn
    return seen


def _live_shapes_run(engine):
    """Batches that grow and shrink through every decode shape and prefill
    width of ``LIVE_CFG``: waves of 4, 2, 1, 2, 3, 1, 3 requests of short,
    middling and long contexts, then a staggered mix. Returns the requests
    in submission order."""
    rng = np.random.default_rng(31)
    reqs = []

    def wave(n, prompt_len, max_new):
        for _ in range(n):
            reqs.append(engine.submit(rng.integers(1, 255, size=prompt_len).astype(np.int32), max_new))
        engine.run_until_idle()

    wave(4, 3, 3)    # 4 rows x 1 block, then 4 x 2
    wave(2, 3, 3)    # 4 x 1 (less to gather than 2 x 4), then 2 x 4
    wave(1, 6, 8)    # 1 x 4
    wave(2, 10, 4)   # 2 x 4
    wave(3, 10, 4)   # 4 x 4
    wave(1, 18, 4)   # 1 x 8, and chunks that reach 1, 2, 3, 4 and 5 blocks
    wave(2, 18, 3)   # 2 x 8
    wave(3, 18, 3)   # 4 x 8
    for prompt_len, max_new in ((5, 9), (17, 4), (2, 12), (9, 6), (13, 3), (1, 7)):
        reqs.append(engine.submit(rng.integers(1, 255, size=prompt_len).astype(np.int32), max_new))
        engine.step()
    engine.run_until_idle()
    return reqs


@pytest.fixture(scope="module")
def live_shapes_run(tiny_lm):
    """The run above through a warmed engine, and through one held to the
    full table shape."""
    cfg, _, params = tiny_lm
    registry = MetricsRegistry()
    engine = ServingEngine(cfg, params, LIVE_CFG, dtype=jnp.float32, registry=registry)
    engine.warmup()
    warmed = registry.snapshot()["serve_compile_total"]
    warm = (engine._decode_fn, engine._prefill_fn)
    seen = _recorded(engine)
    reqs = _live_shapes_run(engine)
    full = _full_shape(ServingEngine(cfg, params, LIVE_CFG, dtype=jnp.float32))
    seen_full = _recorded(full)
    return {
        "engine": engine, "reqs": reqs, "seen": seen, "warmed": warmed, "warm": warm, "snapshot": registry.snapshot(),
        "full_reqs": _live_shapes_run(full), "seen_full": seen_full,
    }


class TestLiveShapes:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "int8"])
    @pytest.mark.parametrize("rows", [1, 2, 4], ids=["smallest", "middle", "max_slots"])
    def test_packed_decode_step_matches_the_full_table(self, tiny_lm, rows, kv_dtype):
        """``decode_step`` on a table packed to (rows, width) returns the
        token of every live row, and writes the pages, of the call at
        (``max_slots``, ``max_blocks_per_seq``) with the same sequences in
        slots apart."""
        cfg, _, params = tiny_lm
        engine = ServingEngine(cfg, params, dataclasses.replace(LIVE_CFG, kv_dtype=kv_dtype), dtype=jnp.float32)
        e = engine.engine
        rng = np.random.default_rng(rows)
        slots = [3, 0][:min(rows, 2)]  # live rows in slots apart and out of order (the smallest bucket holds one)
        prompts = [rng.integers(1, 255, size=n).astype(np.int32) for n in (9, 6)][:len(slots)]
        held = [[7, 2, 11], [5, 9]][:len(slots)]  # blocks out of order, none the scratch block
        tables, lengths, tokens, active = _slot_batch(e, slots, prompts, held)
        kv = _prefilled(engine, prompts, [tables[s] for s in slots])
        width = next(w for r, w in engine._decode_shapes if r == rows and w >= 3)
        packed = [np.zeros((rows, *a.shape[1:]), a.dtype) for a in (tables[:, :width], lengths, tokens, active)]
        for i, slot in enumerate(slots):
            for dst, src in zip(packed, (tables[:, :width], lengths, tokens, active)):
                dst[i] = src[slot]
        kv_full, tok_full, _ = engine._decode_jit(engine.params, _own(kv), *map(jnp.asarray, (tables, lengths, tokens, active)))
        kv_packed, tok_packed, _ = engine._decode_jit(engine.params, _own(kv), *map(jnp.asarray, packed))
        assert tok_packed.shape == (rows,)
        assert [int(tok_packed[i]) for i in range(len(slots))] == [int(tok_full[s]) for s in slots]
        for a, b, before in zip(_pages(kv_packed), _pages(kv_full), _pages(kv)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            assert (a != before).any()  # the step did write

    @pytest.mark.parametrize("chunk", [0, 1, 2], ids=["first", "middle", "ragged_last"])
    def test_prefill_chunk_on_a_table_cut_to_its_reach_matches_the_full_width(self, tiny_lm, chunk):
        """A chunk sees positions below ``start + n_valid``: on the table cut
        to the bucket covering them it returns the last row's logits, and
        writes the pages, of the call at ``max_blocks_per_seq``."""
        cfg, _, params = tiny_lm
        engine = ServingEngine(cfg, params, LIVE_CFG, dtype=jnp.float32)
        e = engine.engine
        prompt = np.random.default_rng(5).integers(1, 255, size=11).astype(np.int32)
        table = np.zeros((e.max_blocks_per_seq,), np.int32)
        table[:3] = [6, 2, 9]  # the request holds its whole prompt's blocks from admission on
        start = chunk * e.prefill_chunk
        kv = _prefilled(engine, [prompt[:start]], [table])
        n = min(e.prefill_chunk, len(prompt) - start)
        tokens = np.zeros((e.prefill_chunk,), np.int32)
        tokens[:n] = prompt[start:start + n]
        width = engine._gather_width(engine.pool.blocks_for(start + n))
        assert width == (1, 2, 4)[chunk] < e.max_blocks_per_seq
        rest = (jnp.asarray(tokens), jnp.int32(start), jnp.int32(n))
        kv_full, logits_full = engine._prefill_jit(engine.params, _own(kv), jnp.asarray(table), *rest)
        kv_cut, logits_cut = engine._prefill_jit(engine.params, _own(kv), jnp.asarray(table[:width]), *rest)
        np.testing.assert_allclose(np.asarray(logits_cut), np.asarray(logits_full), rtol=1e-5, atol=1e-5)
        for a, b, before in zip(_pages(kv_cut), _pages(kv_full), _pages(kv)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            assert (a != before).any()

    def test_streams_are_those_of_the_full_shape_engine(self, live_shapes_run):
        run = live_shapes_run
        assert run["seen_full"] == {"decode": {(4, 8)}, "prefill": {8}}
        assert len(run["reqs"]) == len(run["full_reqs"]) == 24
        for req, full in zip(run["reqs"], run["full_reqs"]):
            assert req.state is full.state is RequestState.FINISHED
            assert req.generated == full.generated, req.rid

    def test_the_run_reaches_every_shape_and_compiles_none(self, live_shapes_run):
        """``warmup()`` traced one program per shape the bucket functions can
        emit; the run reached every one of them and traced nothing."""
        run = live_shapes_run
        engine = run["engine"]
        assert run["seen"]["decode"] == set(engine._decode_shapes)
        assert run["seen"]["prefill"] == set(engine._widths)
        assert run["warmed"] == len(engine._decode_shapes) + len(engine._widths)
        # each through its own executable, none through the jit behind them
        assert [fn.fallback_calls for fn in run["warm"]] == [0, 0]
        assert run["snapshot"]["serve_compile_total"] == run["warmed"]

    def test_the_bucket_functions_emit_only_warmed_shapes(self, tiny_lm):
        """Every (rows that decode, blocks held) maps to the pair of
        ``_decode_shapes`` that holds it in the least rows x width, every
        reach to a width of ``_widths``, and each listed shape is emitted by
        something: the lists ``warmup()`` walks are the sets the step can
        ask for."""
        cfg, _, params = tiny_lm
        for ecfg in (LIVE_CFG, ENGINE_CFG, EngineConfig(max_slots=32, block_size=16, num_blocks=256, max_blocks_per_seq=224)):
            engine = ServingEngine(cfg, params, ecfg, dtype=jnp.float32)
            emitted = set()
            for rows in range(1, ecfg.max_slots + 1):
                for held in range(1, ecfg.max_blocks_per_seq + 1):
                    shape = engine._decode_shape(rows, held)
                    assert shape[0] >= rows and shape[1] >= held
                    assert all(
                        shape[0] * shape[1] <= r * w
                        for r, w in engine._decode_shapes if r >= rows and w >= held
                    )
                    emitted.add(shape)
            assert emitted == set(engine._decode_shapes)
            widths = {engine._gather_width(b) for b in range(1, ecfg.max_blocks_per_seq + 1)}
            assert widths == set(engine._widths)
            assert (ecfg.max_slots, ecfg.max_blocks_per_seq) in emitted
            assert len(emitted) + len(widths) <= 12  # the program budget (the one-axis ladder of PR 28 warmed 10)
        assert engine._widths == (32, 64, 128, 224)  # the benchmark's serving cell
        assert engine._decode_shapes == (
            (8, 128), (8, 224), (16, 128), (16, 224), (32, 32), (32, 64), (32, 128), (32, 224),
        )
        # a full batch of short rows keeps the narrow table it had before the rows were packed
        assert engine._decode_shape(32, 20) == engine._decode_shape(12, 20) == (32, 32)
        assert engine._decode_shape(12, 50) == (16, 128) and engine._decode_shape(5, 20) == (8, 128)

    def test_gather_counters_bound_the_live_blocks(self, live_shapes_run, tiny_lm):
        snap = live_shapes_run["snapshot"]
        assert snap["serve_gather_blocks"] >= snap["serve_live_blocks"] > 0
        # One row of a short prompt, by the buckets: the chunk reaches 1
        # block at width 1; two decode steps hold 1 then 2 blocks in the
        # narrowest table warmed for one row, 1 x 4.
        cfg, _, params = tiny_lm
        registry = MetricsRegistry()
        engine = ServingEngine(cfg, params, LIVE_CFG, dtype=jnp.float32, registry=registry)
        engine.submit(np.arange(1, 4, dtype=np.int32), 3)
        engine.run_until_idle()
        one = registry.snapshot()
        assert engine._gather_width(1) == 1 and engine._decode_shape(1, 2) == (1, 4)
        assert (one["serve_gather_blocks"], one["serve_live_blocks"]) == (1 + 4 + 4, 1 + 1 + 2)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "int8"])
    def test_verify_with_no_proposals_is_the_decode_step(self, tiny_lm, kv_dtype):
        """``verify_step`` at ``W = 1``, ``n_live = 1`` feeds each row its
        last known token alone: it returns ``decode_step``'s tokens and
        leaves the pools as the decode step does (one body, two cores)."""
        cfg, _, params = tiny_lm
        engine = ServingEngine(cfg, params, dataclasses.replace(LIVE_CFG, kv_dtype=kv_dtype), dtype=jnp.float32)
        e = engine.engine
        rng = np.random.default_rng(3)
        slots = [3, 0, 2]  # slot 1 stays inactive
        prompts = [rng.integers(1, 255, size=n).astype(np.int32) for n in (9, 6, 4)]
        held = [[7, 2, 11], [5, 9], [3, 4]]
        tables, lengths, tokens, active = _slot_batch(e, slots, prompts, held)
        kv = _prefilled(engine, prompts, [tables[s] for s in slots])
        tables, lengths, tokens, active = map(jnp.asarray, (tables, lengths, tokens, active))
        kv_dec, tok_dec, _ = engine._decode_jit(engine.params, _own(kv), tables, lengths, tokens, active)
        kv_ver, tok_ver = jax.jit(engine._fwd.verify_step)(
            engine.params, _own(kv), tables, lengths, tokens[:, None], jnp.ones_like(lengths), active,
        )
        assert tok_ver.shape == (e.max_slots, 1)
        assert [int(tok_ver[s, 0]) for s in slots] == [int(tok_dec[s]) for s in slots]
        for a, b, before in zip(_pages(kv_ver), _pages(kv_dec), _pages(kv)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            assert (a != before).any()  # the step did write

    def test_the_verify_step_counts_its_gather_too(self, tiny_lm):
        """A speculating engine gathers ``max_slots`` rows at the widest
        live row's width in each verify step: one short prompt through
        ``ENGINE_CFG`` (3 slots) is a chunk at width 1 that reaches 1 block,
        then verify steps at 3 x 1 and 3 x 2 that hold 1 and 2."""
        registry = MetricsRegistry()
        engine = _spec_engine(tiny_lm, registry=registry)
        req = engine.submit(np.arange(1, 4, dtype=np.int32), 5)
        engine.run_until_idle()
        snap = registry.snapshot()
        steps = snap["spec_verify_steps"]
        assert req.state is RequestState.FINISHED and steps >= 1
        assert snap["serve_gather_blocks"] > snap["serve_live_blocks"] > 1
        assert 1 + 3 * steps <= snap["serve_gather_blocks"] <= 1 + 3 * 2 * steps


# ---- the decode table from the window's first block (PR 35) ----------------
# A window of 16 positions over blocks of 4 lies in 4 or 5 blocks of the 32 a
# sequence may hold: the decode ladder is built on 5 (1, 2, 4, 5), the prefill
# chunk's on 32 (4, 8, 16, 32).
WINDOW = 16
WINDOW_CFG = EngineConfig(
    max_slots=4, block_size=4, num_blocks=128, max_blocks_per_seq=32,
    prefill_chunk=8, max_queue=16,
)
WINDOW_CAP = -(-WINDOW // WINDOW_CFG.block_size) + 1
WINDOW_PROMPTS = (37, 21, 5)  # two past the window at admission, one that crosses it while it decodes
WINDOW_NEW = (60, 45, 40)


@pytest.fixture(scope="module")
def windowed_lm():
    """Grouped KV heads and a sliding window, as the served model has."""
    cfg = dataclasses.replace(TransformerConfig.tiny(), num_kv_heads=2, attention_window=WINDOW)
    model = TransformerLM(config=cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(35), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _first_blocks(lengths):
    """The formula spelled out, apart from the engine's own function."""
    return [max(int(n) - WINDOW, 0) // WINDOW_CFG.block_size for n in lengths]


@pytest.fixture(scope="module")
def window_run(windowed_lm):
    """Three requests through a warmed engine whose window binds, the last
    submitted once the first two decode. Records every decode launch (the
    span's labels, the table's shape, the rows' lengths, the counters'
    rise) and the logits of every request's last prefill chunk."""
    from deeplearning_mpi_tpu.serving import engine as engine_module

    cfg, model, params = windowed_lm
    registry = MetricsRegistry()
    engine = ServingEngine(cfg, params, WINDOW_CFG, dtype=jnp.float32, registry=registry)
    engine.warmup()
    warmed = registry.snapshot()["serve_compile_total"]
    warm = (engine._decode_fn, engine._prefill_fn)
    launches, first_logits = [], {}

    def decode_fn(params, kv, tables, lengths, tokens, active):
        launches[-1].update(shape=tuple(tables.shape), lengths=np.asarray(lengths)[np.asarray(active)])
        return warm[0](params, kv, tables, lengths, tokens, active)

    def prefill_fn(params, kv, table, tokens, start, n_valid):
        kv, logits = warm[1](params, kv, table, tokens, start, n_valid)
        first_logits[int(start) + int(n_valid)] = np.asarray(logits)  # the last chunk's overwrites: keyed by prompt length
        return kv, logits

    def recording_span(name, **labels):
        if name == "serve/decode_launch":
            launches.append({"labels": labels, "skipped_before": registry.snapshot()["serve_window_skipped_blocks"]})
        return real_span(name, **labels)

    engine._decode_fn, engine._prefill_fn = decode_fn, prefill_fn
    real_span, engine_module.span = engine_module.span, recording_span
    try:
        rng = np.random.default_rng(35)
        prompts = [rng.integers(1, 255, size=n).astype(np.int32) for n in WINDOW_PROMPTS]
        reqs = [engine.submit(p, n) for p, n in zip(prompts[:2], WINDOW_NEW)]
        while not all(r.state is RequestState.DECODE for r in reqs):
            engine.step()
        reqs.append(engine.submit(prompts[2], WINDOW_NEW[2]))
        engine.run_until_idle()
    finally:
        engine_module.span = real_span
    snapshot = registry.snapshot()
    for launch, after in zip(launches, [*(l["skipped_before"] for l in launches[1:]), snapshot["serve_window_skipped_blocks"]]):
        launch["skipped_counted"] = after - launch["skipped_before"]
    return {
        "engine": engine, "reqs": reqs, "prompts": prompts, "launches": launches, "first_logits": first_logits,
        "warmed": warmed, "warm": warm, "snapshot": snapshot,
    }


@pytest.fixture(scope="module")
def window_engine(windowed_lm):
    """An engine whose window binds and the same program over whole tables
    (what the draft's propose loop and the parent's decode step run)."""
    from deeplearning_mpi_tpu.serving.engine import PagedForward

    cfg, _, params = windowed_lm
    engine = ServingEngine(cfg, params, WINDOW_CFG, dtype=jnp.float32)
    whole = jax.jit(PagedForward(cfg, WINDOW_CFG, jnp.float32).decode_step)
    return engine, whole


class TestWindowedDecode:
    def test_streams_are_offline_greedy_of_the_windowed_model(self, window_run, windowed_lm):
        """The first tier-1 hold on the engine's window at all: chunked
        prefill (chunks of 8 under a window of 16) and decode from the
        window's first block, token for token ``generate``'s."""
        _, model, params = windowed_lm
        for req, prompt, new in zip(window_run["reqs"], window_run["prompts"], WINDOW_NEW):
            assert req.state is RequestState.FINISHED and req.length == len(prompt) + new > 2 * WINDOW
            assert req.generated == _offline_greedy(model, params, prompt, new), req.rid

    def test_served_tokens_and_first_logits_are_the_uncached_forward_s(self, window_run, windowed_lm):
        """Against ``TransformerLM``'s plain forward over the whole stream
        (no cache, the window as ``dense_attention``'s mask): every served
        token is its argmax at that position, and the last prefill chunk's
        logits are its logits at the prompt's last position."""
        _, model, params = windowed_lm
        for req, prompt in zip(window_run["reqs"], window_run["prompts"]):
            ids = np.concatenate([prompt, np.asarray(req.generated, np.int32)])
            logits = np.asarray(model.apply({"params": params}, jnp.asarray(ids)[None]))[0]
            assert np.argmax(logits[len(prompt) - 1:-1], axis=-1).tolist() == req.generated
            np.testing.assert_allclose(window_run["first_logits"][len(prompt)], logits[len(prompt) - 1], rtol=1e-4, atol=1e-4)

    def test_width_and_skipped_blocks_follow_the_window(self, window_run):
        """A launch's table is as wide as the widest row's blocks from its
        window's first on, never wider than the window's cap once every row
        is past it; ``skipped`` and ``serve_window_skipped_blocks`` are the
        sum of the rows' first blocks, ``serve_live_blocks`` what is left."""
        run, BS = window_run, WINDOW_CFG.block_size
        engine = run["engine"]
        assert engine._fwd.decode_window == WINDOW
        assert engine._widths == (4, 8, 16, 32)
        assert engine._decode_shapes == ((1, 4), (1, 5), (2, 4), (2, 5), (4, 1), (4, 2), (4, 4), (4, 5))
        past = [l for l in run["launches"] if l["lengths"].min() > WINDOW]
        assert len(past) > 40 and any(len(l["lengths"]) == 3 for l in past)
        reach = 0
        for launch in run["launches"]:
            lengths, labels = launch["lengths"], launch["labels"]
            first = _first_blocks(lengths)
            blocks = [-(-int(n) // BS) - f for n, f in zip(lengths, first)]
            assert launch["shape"] == (labels["table_rows"], labels["width"]) == engine._decode_shape(len(lengths), max(blocks))
            assert labels["skipped"] == launch["skipped_counted"] == sum(first)
            assert labels["width"] <= WINDOW_CAP or lengths.min() <= WINDOW
            reach += sum(blocks)
        assert all(l["labels"]["width"] <= WINDOW_CAP for l in past)
        assert max(l["labels"]["skipped"] for l in past) > 3 * WINDOW_CAP  # far more left out than handed over
        prefill_blocks = sum(-(-n // BS) for p in WINDOW_PROMPTS for n in range(WINDOW_CFG.prefill_chunk, p + WINDOW_CFG.prefill_chunk, WINDOW_CFG.prefill_chunk))
        assert run["snapshot"]["serve_live_blocks"] <= reach + prefill_blocks
        assert run["snapshot"]["serve_window_skipped_blocks"] == sum(l["labels"]["skipped"] for l in run["launches"])

    def test_no_compile_while_rows_cross_the_window(self, window_run):
        run = window_run
        engine = run["engine"]
        crossing = [l for l in run["launches"] if l["lengths"].min() <= WINDOW < l["lengths"].max()]
        assert crossing and {l["shape"][1] for l in run["launches"]} >= {4, 5}
        assert run["warmed"] == len(engine._decode_shapes) + len(engine._widths)
        assert run["snapshot"]["serve_compile_total"] == run["warmed"]
        assert [fn.fallback_calls for fn in run["warm"]] == [0, 0]

    @pytest.mark.parametrize("past", [-1, 0, WINDOW_CFG.block_size - 1, WINDOW_CFG.block_size, WINDOW_CFG.block_size + 1])
    def test_host_and_device_agree_on_the_first_block(self, window_engine, past):
        """At ``length - window`` = ``past``: the host's ints, numpy arrays
        and the traced program give one first block, and the step over the
        table cut there returns the token, and writes the page, of the step
        over the whole table."""
        from deeplearning_mpi_tpu.serving.engine import window_first_block

        engine, whole = window_engine
        e, BS = engine.engine, WINDOW_CFG.block_size
        length = WINDOW + past
        first = window_first_block(length, WINDOW, BS)
        assert first == _first_blocks([length])[0] == (0, 0, 0, 1, 1)[(-1, 0, BS - 1, BS, BS + 1).index(past)]
        lengths = np.asarray([length, 1, 3 * WINDOW + past], np.int32)
        on_device = jax.jit(lambda n: window_first_block(n, WINDOW, BS))(jnp.asarray(lengths))
        assert on_device.dtype == jnp.int32
        assert np.asarray(on_device).tolist() == window_first_block(lengths, WINDOW, BS).tolist() == _first_blocks(lengths)
        prompt = np.random.default_rng(length).integers(1, 255, size=length - 1).astype(np.int32)
        held = [9, 3, 14, 6, 11, 2, 8][:-(-length // BS)]  # out of order, none the scratch block
        table = np.zeros((1, e.max_blocks_per_seq), np.int32)
        table[0, :len(held)] = held
        kv = _prefilled(engine, [prompt], [table[0]])
        rest = tuple(jnp.asarray(a) for a in (np.asarray([length], np.int32), np.asarray([23], np.int32), np.asarray([True])))
        cut = np.zeros(engine._decode_shape(1, len(held) - first), np.int32)
        cut[0, :len(held) - first] = held[first:]
        assert cut.shape[1] <= WINDOW_CAP < table.shape[1]
        kv_whole, tok_whole, _ = whole(engine.params, _own(kv), jnp.asarray(table), *rest)
        kv_cut, tok_cut, _ = engine._decode_jit(engine.params, _own(kv), jnp.asarray(cut), *rest)
        assert int(tok_cut[0]) == int(tok_whole[0])
        for a, b, before in zip(_pages(kv_cut), _pages(kv_whole), _pages(kv)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            assert (a != before).any()  # the step did write

    @pytest.mark.parametrize("case", ["max_seq_len_within_the_window", "no_window"])
    def test_a_window_that_cannot_bind_leaves_the_program_and_the_ladder_alone(self, windowed_lm, case):
        """Where no sequence can outgrow the window, or the model has none,
        ``decode_step`` traces to the text of the program over whole tables
        (no first-block arithmetic in it) and the shapes are those of the two
        ceilings alone."""
        from deeplearning_mpi_tpu.serving.engine import PagedForward, _table_shapes

        cfg, _, params = windowed_lm
        if case == "no_window":
            cfg, ecfg = dataclasses.replace(cfg, attention_window=0), WINDOW_CFG
        else:
            ecfg = dataclasses.replace(WINDOW_CFG, max_blocks_per_seq=WINDOW // WINDOW_CFG.block_size)
            assert ecfg.max_seq_len == cfg.attention_window
        engine = ServingEngine(cfg, params, ecfg, dtype=jnp.float32)
        assert engine._fwd.decode_window == 0
        assert (engine._widths, engine._decode_shapes) == _table_shapes(ecfg.max_slots, ecfg.max_blocks_per_seq)
        assert engine._decode_shapes[-1] == (ecfg.max_slots, ecfg.max_blocks_per_seq)
        rows, width = engine._decode_shapes[0]
        args = (
            engine.params, engine._kv, jnp.zeros((rows, width), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), bool),
        )
        whole = PagedForward(cfg, ecfg, jnp.float32)
        text = str(jax.make_jaxpr(engine._fwd.decode_step)(*args))
        assert text == str(jax.make_jaxpr(whole.decode_step)(*args))
        # the control: a window that binds does change the trace
        binding = ServingEngine(windowed_lm[0], params, WINDOW_CFG, dtype=jnp.float32)
        assert str(jax.make_jaxpr(binding._fwd.decode_step)(*args)) != str(
            jax.make_jaxpr(PagedForward(windowed_lm[0], WINDOW_CFG, jnp.float32).decode_step)(*args)
        )

    def test_the_long_session_cell_s_ladder(self, windowed_lm):
        """At the benchmark's long-session engine (32 slots, 1,152 blocks of
        16 a sequence, a window of 4,096): the decode step is warmed up to
        257 blocks a row, in no more pairs than up to 1,152; the prefill
        chunk's widths stay."""
        from deeplearning_mpi_tpu.serving.engine import _table_shapes, window_blocks

        assert window_blocks(4096, 16) == 257 and window_blocks(WINDOW, WINDOW_CFG.block_size) == WINDOW_CAP
        widths, pairs = _table_shapes(32, 1152, 257)
        assert widths == (256, 512, 1024, 1152) == _table_shapes(32, 1152)[0]
        assert pairs == ((8, 256), (8, 257), (16, 256), (16, 257), (32, 64), (32, 128), (32, 256), (32, 257))
        assert len(pairs) == len(_table_shapes(32, 1152)[1])
        cfg, _, params = windowed_lm
        engine = ServingEngine(
            dataclasses.replace(cfg, attention_window=4096), params,
            EngineConfig(max_slots=32, block_size=16, num_blocks=1154, max_blocks_per_seq=1152), dtype=jnp.float32,
        )
        assert (engine._widths, engine._decode_shapes) == (widths, pairs)
        assert engine._decode_shape(8, 257) == (8, 257) and engine._decode_shape(8, 256) == (8, 256)

    def test_the_draft_keeps_whole_tables_and_speculation_keeps_parity(self, windowed_lm):
        """The verify step and the draft's propose loop read tables from
        block 0; the plain decode step a suspended speculation falls back to
        reads them from the window's first block. Switching between the two
        mid-stream leaves the tokens offline greedy's."""
        cfg, model, params = windowed_lm
        engine = _spec_engine(windowed_lm, spec_k=2, base_cfg=WINDOW_CFG)
        assert engine._fwd.decode_window == WINDOW and engine._spec._fwd.decode_window == 0
        rng = np.random.default_rng(53)
        prompts = [rng.integers(1, 255, size=n).astype(np.int32) for n in (19, 7)]
        reqs = [engine.submit(p, 36) for p in prompts]
        for stage in (0, 2, 0, 2):
            engine.set_brownout(stage)
            for _ in range(9):
                engine.step()
        engine.set_brownout(0)
        engine.run_until_idle()
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            assert req.generated == _offline_greedy(model, params, prompt, 36), req.rid
