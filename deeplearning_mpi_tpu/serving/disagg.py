"""Disaggregated prefill/decode serving: two engines, one KV pool.

The colocated :class:`~deeplearning_mpi_tpu.serving.engine.ServingEngine`
interleaves chunked prefill and batched decode inside one step loop, which
couples the two phases' latency: every prompt chunk a step spends is a
step the decode batch does not advance, so a burst of long prompts shows
up directly as TPOT jitter for every in-flight sequence (the "prefill
stall" every production stack fights — DistServe, Splitwise; PAPERS.md).
Disaggregation splits the loop by ROLE:

- :class:`PrefillEngine` runs admission + chunked prefill ONLY. When a
  prompt completes (first token emitted from the final chunk's logits, so
  TTFT is measured where the work happened), the request is *detached*
  from its slot and pushed onto a handoff queue.
- :class:`DecodeEngine` runs KV growth + batched decode (or the
  speculative propose/verify loop) ONLY, over fixed-shape programs whose
  batch never stalls behind a long prompt. It *adopts* handoff requests
  into free slots.
- :class:`DisaggregatedEngine` owns both, drives the handoff between
  them, and presents the colocated engine's public surface (``submit`` /
  ``step`` / ``run_until_idle`` / ``recover`` / ``warmup``).

The handoff itself moves **no KV bytes**. Both engines are constructed
over one shared :class:`~deeplearning_mpi_tpu.serving.kv_pool.PagedKVPool`
and one shared :class:`~deeplearning_mpi_tpu.serving.engine.KVBuffers`
holder, so a completed prefill's pages are already exactly where decode
will gather them — the handoff transfers *block-table ownership* (the
request object carries its block list), nothing else. This is the
single-host analogue of the NVLink/ICI page transfer a multi-host
disaggregated deployment would do, and it keeps the design testable on
CPU: the e2e test pins handoff streams bit-identical to the colocated
engine's.

Each role keeps its own compiled programs, its own warmup, and its own
``role=...`` gauges in the shared registry.

Chaos: the ``handoff_stall`` fault kind (``--chaos handoff_stall@step:N``)
wedges the handoff queue — completed prefills pile up while the decode
batch drains — until the coordinator notices the stuck queue and records
the recovery, exercising exactly the cross-role seam colocated serving
does not have. ``serve_crash`` keeps firing inside the prefill engine's
step (admission + partial prefills mid-flight is still the nastiest crash
point); :meth:`DisaggregatedEngine.recover` requeues in-flight work from
BOTH engines and the handoff queue back through prefill, reconciling the
one shared pool.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Optional

import jax.numpy as jnp

from deeplearning_mpi_tpu.models.transformer import TransformerConfig
from deeplearning_mpi_tpu.serving.engine import (
    EngineConfig,
    KVBuffers,
    ServingEngine,
)
from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool, init_kv_buffers
from deeplearning_mpi_tpu.serving.scheduler import Request

__all__ = ["DecodeEngine", "DisaggregatedEngine", "PrefillEngine"]


class PrefillEngine(ServingEngine):
    """The prefill role: admission + chunked prefill, never decode.

    A request whose prompt completes (and that still has tokens to
    generate) is detached from its slot — KV blocks travel with it — and
    appended to :attr:`handoff` for the decode peer to adopt. Requests
    that finish AT their first token (``max_new_tokens == 1`` or an
    immediate EOS) never hand off at all; prefill retires them itself.
    """

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        kwargs.setdefault("role", "prefill")
        super().__init__(*args, **kwargs)
        #: completed prefills awaiting adoption, FIFO
        self.handoff: deque[Request] = deque()

    def _prefill_complete(self, req: Request) -> None:
        req.t_detached = self._clock()
        self.scheduler.detach(req)
        self.handoff.append(req)

    def step(self) -> list[Request]:
        """Admission + prefill chunks + the chaos crash hook. No decode
        phase: this role's step cost is bounded by chunk width alone."""
        now = self._clock()
        finished: list[Request] = []
        self._phase_admit(now)
        self._phase_cow()
        self._phase_prefill(finished)
        self._phase_chaos()
        self.steps += 1
        self._set_gauges()
        return finished


class DecodeEngine(ServingEngine):
    """The decode role: KV growth + batched decode / speculative verify
    over adopted sequences, never admission or prefill. Its scheduler's
    queue stays empty by construction — supply arrives only through
    :meth:`adopt`."""

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        kwargs.setdefault("role", "decode")
        super().__init__(*args, **kwargs)

    def adopt(self, req: Request) -> bool:
        """Install a handed-off request into a free slot (False = full;
        the coordinator retries next step)."""
        return self.scheduler.adopt(req)

    def step(self) -> list[Request]:
        finished: list[Request] = []
        decoding = self._phase_grow()
        self._phase_decode(decoding, finished)
        self.steps += 1
        self._set_gauges()
        return finished


class DisaggregatedEngine:
    """Coordinator over one prefill engine + one decode engine sharing a
    KV pool. Public surface mirrors :class:`ServingEngine` (``submit`` /
    ``cancel`` / ``step`` / ``run_until_idle`` / ``recover`` /
    ``warmup``), so the CLI, the fleet worker, and the benchmarks drive
    either topology through the same calls.

    One coordinator step advances prefill, drains the handoff queue into
    free decode slots (oldest first, stopping at the first refusal), then
    advances decode — so a prompt's final chunk and its first decode step
    land in CONSECUTIVE engine steps, same as colocated, which is what
    makes the bit-identical-streams test meaningful rather than merely
    eventually-equal.
    """

    def __init__(
        self,
        config: TransformerConfig,
        params: Any,
        engine: EngineConfig | None = None,
        *,
        dtype: Any = jnp.bfloat16,
        eos_id: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        registry: Any = None,
        chaos: Any = None,
        draft_config: TransformerConfig | None = None,
        draft_params: Any = None,
        tenants: dict[str, dict[str, Any]] | None = None,
        tracer: Any = None,
    ) -> None:
        engine = engine or EngineConfig()
        storage = jnp.dtype(engine.kv_dtype) if engine.kv_dtype else None
        self.engine = engine
        self.config = config
        self.chaos = chaos
        self.steps = 0
        self._metrics = registry
        self._clock = clock
        self._tracer = tracer
        self._stall_observed = False
        # ONE pool + ONE set of device buffers, shared by both roles: the
        # handoff transfers block-table ownership over pages that are
        # already in place.
        self.pool = PagedKVPool(
            engine.num_blocks, engine.block_size, kv_dtype=storage
        )
        kvh = KVBuffers(init_kv_buffers(
            config.num_layers, engine.num_blocks, engine.block_size,
            config.num_kv_heads or config.num_heads, config.head_dim,
            storage if storage is not None else dtype,
        ))
        draft_kvh = None
        if engine.spec_k > 0 and draft_config is not None:
            draft_kvh = KVBuffers(init_kv_buffers(
                draft_config.num_layers, engine.num_blocks,
                engine.block_size,
                draft_config.num_kv_heads or draft_config.num_heads,
                draft_config.head_dim,
                storage if storage is not None else dtype,
            ))
        # ONE prefix cache over the one shared pool: prefill inserts the
        # full-block span at prompt completion, decode inserts the frozen
        # partial tail at finish, and both index the same trie — a hit
        # admitted at the prefill role adopts pages the decode role's
        # requests froze. Built here (not per role) so neither engine
        # constructs a private cache over the shared pool.
        self.prefix_cache = None
        if engine.prefix_cache:
            from deeplearning_mpi_tpu.serving.prefix_cache import (
                RadixPrefixCache,
            )

            self.prefix_cache = RadixPrefixCache(self.pool, registry=registry)
        common = dict(
            dtype=dtype, eos_id=eos_id, clock=clock, registry=registry,
            draft_config=draft_config, draft_params=draft_params,
            pool=self.pool, kv_buffers=kvh, draft_kv_buffers=draft_kvh,
            prefix_cache=self.prefix_cache, tenants=tenants,
            tracer=tracer,
        )
        # serve_crash chaos stays with the prefill role — mid-admission +
        # partial prefill is the crash point recover() must untangle; the
        # handoff_stall kind belongs to the coordinator, not either engine.
        self.prefill = PrefillEngine(config, params, engine, chaos=chaos, **common)
        self.decode = DecodeEngine(config, params, engine, **common)
        if registry is not None:
            registry.gauge("serve_handoff_depth")
            registry.counter("serve_handoffs_total")
            registry.counter("serve_handoff_stalls_total")
            for name in (
                "serve_queue_depth", "serve_slots_active",
                "serve_kv_blocks_in_use", "serve_kv_bytes",
            ):
                registry.gauge(name)

    # -- public API ----------------------------------------------------------
    def submit(self, prompt: Any, max_new_tokens: int, **kwargs: Any) -> Request:
        """Enqueue one request at the prefill role (the only door in)."""
        return self.prefill.submit(prompt, max_new_tokens, **kwargs)

    def cancel(self, req: Request) -> bool:
        """Shed ``req`` wherever it currently lives: prefill queue/slots,
        the handoff queue, or a decode slot."""
        if req in self.prefill.handoff:
            self.prefill.handoff.remove(req)
            if req.blocks:
                self.pool.free(req.blocks)
                req.blocks = list(req.blocks)
            self.prefill.scheduler._shed(req, "cancelled")
            self.prefill._inc("serve_requests_shed")
            return True
        return self.prefill.cancel(req) or self.decode.cancel(req)

    def set_brownout(self, stage: int) -> None:
        """Apply the brownout ladder to both roles. Admission only happens
        at the prefill door, but the decode scheduler carries the stage
        too so telemetry and policy reads agree across the split."""
        self.prefill.set_brownout(stage)
        self.decode.set_brownout(stage)

    @property
    def handoff_depth(self) -> int:
        return len(self.prefill.handoff)

    @property
    def params(self) -> Any:
        return self.prefill.params

    @params.setter
    def params(self, value: Any) -> None:
        # Hot weight swap (fleet `swap` op): both roles serve the same
        # model, so a swap must land on both atomically w.r.t. step().
        # Cached prefix KV was computed under the OLD weights — bit-wrong
        # under the new ones — so the swap flushes the shared cache.
        self.prefill.params = value
        self.decode.params = value
        if self.prefix_cache is not None:
            self.prefix_cache.flush()

    def step(self) -> list[Request]:
        """One coordinated iteration: prefill step → handoff drain →
        decode step. Returns everything that FINISHED, both roles."""
        finished = list(self.prefill.step())
        self._drain_handoff()
        finished.extend(self.decode.step())
        self.steps += 1
        self._set_gauges()
        return finished

    def _drain_handoff(self) -> None:
        if self.chaos is not None and self.chaos.check_handoff_stall(
            step=self.steps
        ):
            if not self._stall_observed:
                # The wedge: completed prefills stay queued this step while
                # decode drains whatever it already holds.
                self._stall_observed = True
                self._inc("serve_handoff_stalls_total")
                return
            # Second sighting of the stuck queue — the coordinator's
            # "restart the transport": record the recovery and fall
            # through to a normal drain.
            self.chaos.record_recovery("handoff_stall")
            self._stall_observed = False
        q = self.prefill.handoff
        while q:
            req = q[0]
            if not self.decode.adopt(req):
                break  # decode slots full; retry next step (backpressure)
            req.t_adopted = self._clock()
            q.popleft()
            self._inc("serve_handoffs_total")

    def run_until_idle(self, *, max_steps: int = 100_000) -> list[Request]:
        """Step until both roles and the handoff queue drain; injected
        crashes are recovered in place (same contract as the colocated
        engine's ``run_until_idle``)."""
        from deeplearning_mpi_tpu.resilience.faults import InjectedFault

        finished: list[Request] = []
        steps = 0
        while not self.idle():
            try:
                finished.extend(self.step())
            except InjectedFault as err:
                print(f"serving: {err} — recovering")
                self.recover()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"disaggregated engine did not drain within "
                    f"{max_steps} steps"
                )
        return finished

    def idle(self) -> bool:
        return (
            self.prefill.scheduler.idle()
            and not self.prefill.handoff
            and self.decode.scheduler.idle()
        )

    def warmup(self, *, cache: Any = None) -> dict[str, Any]:
        """AOT-compile each role's own programs (prefill first). The two
        warmups are independent by design — per-role program mixes, per-
        role compile accounting — but a shared ``cache`` deduplicates the
        byte-identical lowerings between them."""
        programs = dict(self.prefill.warmup(cache=cache))
        programs.update(
            (f"decode_role_{k}", v)
            for k, v in self.decode.warmup(cache=cache).items()
        )
        return programs

    def recover(self) -> dict[str, int]:
        """Crash recovery across both roles: vacate every slot, clear the
        handoff queue, requeue everything in-flight through prefill
        (oldest-first at the queue front, so FCFS survives), and rebuild
        the one shared pool's free list from scratch. Same trust argument
        as the colocated engine: after a mid-step crash no KV write can be
        proven to have landed, so every sequence re-prefills from its
        prompt — which keeps recovered completions bit-identical to
        offline greedy decode."""
        pre, dec = self.prefill, self.decode
        inflight = sorted(
            {
                r.rid: r
                for r in (
                    *pre.scheduler.running(),
                    *pre.handoff,
                    *dec.scheduler.running(),
                )
            }.values(),
            key=lambda r: (r.arrival, r.rid),
        )
        discarded = sum(len(r.generated) for r in inflight)
        pre.handoff.clear()
        for sched in (pre.scheduler, dec.scheduler):
            for req in list(sched.running()):
                sched.slots[req.slot] = None
                req.slot = None
        for req in reversed(inflight):
            pre.scheduler.requeue(req)
        # Cached pages are proven-landed (each insert follows the owning
        # prefill's first-token sync), so the shared cache SURVIVES the
        # crash: reconcile rebuilds the free list and refcounts around it,
        # and the requeued requests re-match it on re-admission. Pending
        # CoW pins are dropped (their pinned sources are either cache
        # references that survive or in-flight privates that reconcile
        # reclaims).
        pre.scheduler.clear_pending_cow()
        dec.scheduler.clear_pending_cow()
        live: list[int] = []
        if self.prefix_cache is not None:
            live = self.prefix_cache.referenced_blocks()
        stats = self.pool.reconcile(live)
        self.pool.check()
        pre._inc("serve_requeued_total", len(inflight))
        pre._inc("serve_tokens_discarded_total", discarded)
        if self.chaos is not None:
            self.chaos.record_recovery("serve_crash")
        self._set_gauges()
        out = {"requeued": len(inflight), "tokens_discarded": discarded, **stats}
        print(
            f"serving: recovered — requeued {out['requeued']} in-flight "
            f"request(s) through prefill, reclaimed {stats['reclaimed']} "
            f"KV block(s), discarded {discarded} token(s)"
        )
        return out

    # -- telemetry -----------------------------------------------------------
    def _inc(self, name: str, amount: float = 1.0) -> None:
        if self._metrics is not None and amount:
            self._metrics.counter(name).inc(amount)

    def _set_gauges(self) -> None:
        if self._metrics is None:
            return
        # The combined (unlabeled) view the colocated engine would report;
        # per-role occupancy lives under the role=... gauges each engine
        # maintains itself.
        self._metrics.gauge("serve_handoff_depth").set(self.handoff_depth)
        self._metrics.gauge("serve_queue_depth").set(
            self.prefill.scheduler.queue_depth()
        )
        self._metrics.gauge("serve_slots_active").set(
            self.prefill.scheduler.slots_active()
            + self.decode.scheduler.slots_active()
        )
        self._metrics.gauge("serve_kv_blocks_in_use").set(self.pool.in_use)
        self._metrics.gauge("serve_kv_bytes").set(self.prefill._kvh.nbytes)
