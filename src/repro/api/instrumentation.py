"""Phase timing, profiler spans and compile counters for one run.

The legacy driver interleaved ``time.perf_counter()`` stamps with the phase
code itself, which made the phases impossible to reuse (and misattributed
baseline hash cost to the shingle phase).  The engine's stages are pure;
all timing goes through this recorder, so the same stage objects are
jit-cacheable across repeated ``engine.run`` calls with identical static
shapes.

Every phase is also a host span ``phase.<name>`` in any active
``jax.profiler`` trace, on the same clock as the device ops, so an idle
gap on the device can be put down to the phase the host was in.  With no
trace active a span costs about a microsecond.

Stats key conventions:

  t_encode       phase (i)   semantic encoding
  t_keys         phase (ii)a join-key construction (shingles / signatures /
                             projections; 0 for callable backends)
  t_join         phase (ii)b sort-merge join + dedup (+ overflow retries)
  t_candidates   t_keys + t_join — the full candidate-generation cost,
                 correct for every backend (fixes the Fig. 9 misattribution)
  t_score        phase (iii) similarity scoring
  t_results      the similar-pair set on the host: the device-to-host copy
                 of the scored buffers and the set build (the subtrajectory
                 fold included)
  t_communities  phase (iv)  community detection

Sharded runs stage the join and score as shard_map programs; they record
``t_plan`` (the key shuffle program, whose counts plan the rest) and
``t_execute`` (the join and score programs and the score plan between
them), which holds ``t_join`` (the join program, shuffle 2 and the dedup,
ending in the copy of its counts) and ``t_score`` (the owner hops and the
LCS, ending in a device sync); ``t_candidates`` then covers keys + plan +
join.  ``t_keys`` is 0 there unless the backend builds its keys on the
host.  Their counters, made in the mesh:

  exchange           {round: [rows carried, slots shipped]} for each
                     all_to_all round: key_route, dedup_route and, in
                     shuffle mode, owner_hop1 and owner_hop2
  scored_per_chip    the pairs scored on each chip; scored_max and
                     scored_min their largest and smallest
  shard_plan         every capacity of the plan the job ran; n_shards

Compile counters (no ``t_`` prefix: they are not the wall time of a
phase), over the ``with Instrumentation()`` block of one run:

  compiles           backend compiles
  compile_s          seconds of tracing, lowering and backend compile
  compiles_by_phase  {phase: [compiles, seconds]} for each phase in which
                     anything traced, lowered or compiled; a compile inside
                     the run but outside every phase counts under ``run``
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = frozenset({
    BACKEND_COMPILE,
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
})

# per thread: ``open`` is the stack of (recorder, phase) the thread is in;
# ``spans`` the (start, end) of the compile events counted since that
# stack last changed.  JAX fires its compile events synchronously on the
# thread that compiles.
_local = threading.local()
_listening = False


def _listen() -> None:
    """Register the process-wide compile listener, once."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_compile_span)
        _listening = True


def _on_compile_span(event, start, end, **kwargs) -> None:
    """Count a compile event into the innermost phase open on this thread.

    Tracing nests (a jitted function traces the jitted functions it calls,
    each with an event of its own, which ends first), so an event counts
    only the seconds that the events it encloses have not counted."""
    stack = getattr(_local, "open", None)
    if event not in COMPILE_EVENTS or not stack:
        return
    spans = _local.spans
    inner = 0.0
    while spans and spans[-1][0] >= start:
        s, e = spans.pop()
        inner += e - s
    spans.append((start, end))
    instr, name = stack[-1]
    counts = instr.compiles_by_phase.setdefault(name, [0, 0.0])
    counts[0] += event == BACKEND_COMPILE
    counts[1] += max(end - start - inner, 0.0)


class Instrumentation:
    """Per-phase wall times, profiler spans, compile counts and scalar
    stats for one run.

    Enter it (``with Instrumentation() as instr:``) around the whole run,
    so a compile outside every phase still counts (under ``run``)."""

    def __init__(self) -> None:
        self.stats: dict = {}
        self.compiles_by_phase: dict = {}

    @contextlib.contextmanager
    def _counting(self, name: str):
        """Count the compiles this thread makes inside the block under
        ``name``, unless a block opened inside it takes them."""
        _listen()
        if getattr(_local, "open", None) is None:
            _local.open = []
        _local.open.append((self, name))
        _local.spans = []
        try:
            yield
        finally:
            _local.open.pop()
            _local.spans = []

    def __enter__(self) -> "Instrumentation":
        self._run = self._counting("run")
        self._run.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._run.__exit__(*exc)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase (re-entering a name accumulates), span it as
        ``phase.<name>`` and count its compiles.  Phases may nest."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"phase.{name}"), \
                    self._counting(name):
                yield
        finally:
            key = f"t_{name}"
            self.stats[key] = self.stats.get(key, 0.0) + time.perf_counter() - t0

    # the program emits its own phase spans; nothing needs to wrap it
    phase.annotated = True

    def record(self, **values) -> None:
        self.stats.update(values)

    def exchange(self, name: str, rows: int, slots: int) -> None:
        """Count one ``all_to_all`` round: the rows it carried and the
        slots it shipped (``n_shards**2`` times its bucket capacity)."""
        self.stats.setdefault("exchange", {})[name] = [rows, slots]

    def finalize(self) -> dict:
        """Derive the composite keys and return the stats dict."""
        s = self.stats
        s.setdefault("t_keys", 0.0)
        if "t_execute" in s:  # sharded: the key route plans the join
            s["t_candidates"] = (
                s["t_keys"] + s.get("t_plan", 0.0) + s["t_join"]
            )
        elif "t_join" in s:
            s["t_candidates"] = s["t_keys"] + s["t_join"]
        by_phase = {k: list(v) for k, v in self.compiles_by_phase.items()}
        s["compiles"] = sum(n for n, _ in by_phase.values())
        s["compile_s"] = sum(t for _, t in by_phase.values())
        s["compiles_by_phase"] = by_phase
        return s
