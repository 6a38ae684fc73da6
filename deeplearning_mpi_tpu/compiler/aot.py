"""AOT compilation + warmup registry: pay compile cost before traffic.

``jax.jit`` compiles lazily — the first trainer step and the first serving
request each stall for the full XLA compile (seconds on CPU, minutes for
large pods). The AOT path (``jit(f).lower(args).compile()``) moves that
stall to an explicit warmup phase, and the resulting ``Compiled`` object is
directly callable and never retraces — which is also what makes "zero
compiles on the first request" an assertable property rather than a hope.

Three layers:

- :func:`compile_program` — lower+compile one program, timing both phases,
  classifying the compile as a persistent-cache hit or miss (via
  :class:`~deeplearning_mpi_tpu.compiler.cache.CompileCache` snapshots) and
  pulling XLA's own cost analysis (FLOPs / bytes accessed) through
  ``telemetry/flops.xla_cost_analysis`` — the measured complement to the
  analytic estimators.
- :class:`WarmProgram` — the callable swapped into hot paths: the compiled
  executable on the fast path (one per static shape where a function is
  warmed at several), falling back to the original jitted callable if an
  argument signature ever drifts (AOT executables reject unseen avals with
  a TypeError instead of retracing).
- :class:`WarmupRegistry` — named programs registered with their example
  arguments, compiled in one ``warm_all()`` sweep; how the trainer step and
  both serving programs (decode step, chunked prefill) precompile before
  traffic (``Trainer.warmup`` / ``ServingEngine.warmup``).

Donation: :func:`compile_program` applies the
:func:`~deeplearning_mpi_tpu.compiler.cache.donation_safe` veto before
jitting — an AOT program under a persistent cache is the cache-deserialized
executable the veto exists for. Already-jitted callables keep whatever
donation they were built with (their constructors route through the same
policy via ``runtime/compat.buffer_donation_supported``).
"""

from __future__ import annotations

import dataclasses
import re
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from deeplearning_mpi_tpu.compiler.cache import CompileCache, donation_safe

__all__ = [
    "CompiledProgram",
    "WarmProgram",
    "WarmupRegistry",
    "abstractify",
    "collective_counts",
    "compile_program",
    "mosaic_call_count",
]


def mosaic_call_count(compiled: Any, kernel: str | None = None) -> int:
    """Mosaic (Pallas TPU) custom calls in a compiled executable's HLO —
    the evidence that a kernel was *taken*, not merely available: the Pallas
    entry points return their XLA references without a word when a block
    does not tile, and the interpreter lowers to plain HLO (count 0).
    ``kernel``: only the calls of the kernel of that name (``pallas_call``'s
    ``name``: its instructions are ``kernel`` and ``kernel.N``)."""
    text = compiled.as_text()
    if kernel is None:
        return text.count('custom_call_target="tpu_custom_call"')
    call = re.compile(rf'%{re.escape(kernel)}(\.\d+)? = .*custom_call_target="tpu_custom_call"')
    return sum(1 for line in text.splitlines() if call.search(line))


_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
#: the opcode of an HLO instruction line: the first lowercase word after the
#: ``=`` that opens a parenthesis (layouts' ``T(8,128)`` are not preceded by
#: a space, tuple types' element types open brackets).
_OPCODE = re.compile(r"=\s.*?\s([a-z][a-z0-9-]*)\(")


def collective_counts(compiled: Any) -> tuple[int, int]:
    """``(asynchronous, synchronous)`` collectives of a compiled executable's
    scheduled HLO — the evidence that the train step's compile options
    engaged (``train.trainer.step_compiler_options``).

    Asynchronous: the starts of the TPU compiler's async collective fusions
    (``async-collective-start``: a collective split into steps that run
    inside the computations scheduled between its start and its done) and of
    plain async collectives (``all-reduce-start`` and kin). Synchronous: a
    collective instruction outside any fused computation, which holds the
    device until it ends. The collectives inside fused computations are the
    steps of an async fusion, so they count once, at its start."""
    fused: set[str] = set()
    instructions: list[tuple[str, str, str]] = []  # computation, name, opcode
    computation = None
    for line in compiled.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            words = line.split()
            computation = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
        elif line.startswith(" ") and computation is not None:
            m = _OPCODE.search(line)
            if m is None:
                continue
            name = line.split("=", 1)[0].split()[-1].lstrip("%")
            instructions.append((computation, name, m.group(1)))
            if m.group(1) == "fusion":
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    n_async = n_sync = 0
    for computation, name, opcode in instructions:
        if computation in fused:
            continue
        if opcode in _COLLECTIVES:
            n_sync += 1
        elif (opcode.removesuffix("-start") in _COLLECTIVES
              or name.startswith("async-collective-start")):
            n_async += 1
    return n_async, n_sync


def abstractify(tree: Any) -> Any:
    """Arrays (or anything shaped) -> ``ShapeDtypeStruct`` pytree, so
    programs can be lowered without materializing example inputs."""
    def one(x: Any) -> jax.ShapeDtypeStruct:
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    return jax.tree.map(one, tree)


@dataclasses.dataclass
class CompiledProgram:
    """One AOT-compiled executable plus everything warmup learned about it."""

    name: str
    compiled: Any  # jax.stages.Compiled — directly callable, never retraces
    lower_seconds: float
    compile_seconds: float
    #: XLA cost analysis (None where the backend doesn't expose it) — the
    #: executed FLOPs/bytes, not the analytic estimate.
    flops: float | None
    bytes_accessed: float | None
    #: persistent-cache verdict: True deserialized, False compiled fresh,
    #: None when no cache directory is configured.
    cache_hit: bool | None
    #: donate_argnums actually applied (after the donation_safe veto); for
    #: pre-jitted callables this is always () — they own their donation.
    donated: tuple[int, ...]

    def __call__(self, *args: Any) -> Any:
        return self.compiled(*args)


def compile_program(
    name: str,
    fn: Callable[..., Any],
    *args: Any,
    donate_argnums: tuple[int, ...] = (),
    registry: Any = None,
    cache: CompileCache | None = None,
    **jit_kwargs: Any,
) -> CompiledProgram:
    """Lower and compile ``fn`` for ``args`` (concrete arrays or
    ``ShapeDtypeStruct`` trees) ahead of time.

    ``fn`` may be a plain callable (jitted here, with ``donate_argnums``
    subject to the :func:`donation_safe` veto) or an already-jitted one
    (used as-is — it already routed donation through the same policy).
    ``registry``/``cache`` wire the ``compile_*`` telemetry; when ``cache``
    is omitted one is built over the configured cache dir so hit/miss
    classification works out of the box.
    """
    if cache is None:
        cache = CompileCache(registry=registry)
    elif registry is None:
        registry = cache.registry
    donated = tuple(donate_argnums)
    if hasattr(fn, "lower"):
        jitted = fn
        donated = ()  # pre-jitted: donation baked in at construction
    else:
        if donated and not donation_safe():
            donated = ()
        jitted = jax.jit(fn, donate_argnums=donated, **jit_kwargs)
    before = cache.snapshot()
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    hit = cache.observe_compile(name, t2 - t1, before)
    from deeplearning_mpi_tpu.telemetry.flops import xla_cost_analysis

    costs = xla_cost_analysis(compiled)
    return CompiledProgram(
        name=name,
        compiled=compiled,
        lower_seconds=t1 - t0,
        compile_seconds=t2 - t1,
        flops=costs.get("flops"),
        bytes_accessed=costs.get("bytes_accessed"),
        cache_hit=hit,
        donated=donated,
    )


class WarmProgram:
    """The warmed callable: AOT executable first, original jit as a net.

    A ``Compiled`` object raises ``TypeError`` on argument avals it wasn't
    compiled for (AOT never retraces); the fallback keeps a signature drift
    — a config change, an unexpected dtype — a silent recompile instead of
    a crash. ``fallback_calls`` counts how often the net was needed (zero
    in a correctly-warmed engine), ``on_fallback`` is called each time (the
    serving engine's ``serve_program_fallbacks`` counter), and ``last``
    names the executable the latest call ran (None where the net ran it).

    A function warmed at several static shapes hands in a dict of programs
    keyed by the shape of its ``shape_arg``-th argument (of the first array,
    where that argument is a tuple of arrays): the call picks its
    executable by that shape, so no listed shape pays a raised and caught
    exception on its way to the program."""

    def __init__(
        self,
        program: CompiledProgram | dict[tuple[int, ...], CompiledProgram],
        fallback: Callable[..., Any],
        *,
        shape_arg: int | None = None,
        on_fallback: Callable[[], None] | None = None,
    ):
        self.program = program
        self.fallback = fallback
        self.shape_arg = shape_arg
        self.on_fallback = on_fallback
        self.fallback_calls = 0
        self.last: str | None = None

    def __call__(self, *args: Any) -> Any:
        program = self.program
        if self.shape_arg is not None:
            key = args[self.shape_arg]
            if isinstance(key, tuple):  # several arrays: the first one's shape
                key = key[0]
            program = program.get(key.shape)
        if program is not None:
            try:
                out = program.compiled(*args)
            except TypeError:
                pass
            else:
                self.last = program.name
                return out
        self.fallback_calls += 1
        self.last = None
        if self.on_fallback is not None:
            self.on_fallback()
        return self.fallback(*args)


class WarmupRegistry:
    """Named programs + example args, compiled in one sweep before traffic.

    ``register`` is cheap (no tracing); ``warm_all`` pays every lower +
    compile, records ``compile_*`` telemetry through the shared ``cache``,
    and keeps the results addressable by name. Registering a name twice
    replaces the earlier spec (last writer wins — e.g. re-warming after a
    config change)."""

    def __init__(
        self, *, registry: Any = None, cache: CompileCache | None = None
    ):
        self.cache = cache if cache is not None else CompileCache(
            registry=registry
        )
        self.registry = registry if registry is not None else self.cache.registry
        self._specs: dict[str, tuple[Callable[..., Any], tuple, dict]] = {}
        self.programs: dict[str, CompiledProgram] = {}

    def register(
        self,
        name: str,
        fn: Callable[..., Any],
        *args: Any,
        **jit_kwargs: Any,
    ) -> None:
        self._specs[name] = (fn, args, jit_kwargs)

    def warm_all(self) -> dict[str, CompiledProgram]:
        for name, (fn, args, jit_kwargs) in self._specs.items():
            self.programs[name] = compile_program(
                name, fn, *args,
                registry=self.registry, cache=self.cache, **jit_kwargs,
            )
        return dict(self.programs)

    def get(self, name: str) -> CompiledProgram:
        return self.programs[name]
