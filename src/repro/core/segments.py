"""Segment compiler: one pass from a scheduled graph to executable segments.

The compiled executors (`repro.core.pingpong`, `repro.quant.exec`) do not
dispatch per layer: they partition the schedule into **segments**, each of
which traces to a constant number of XLA ops regardless of how many layers
it covers.  This module is the single implementation of that partition —
it replaces the former ``planner.scan_segments`` / ``pingpong._dag_scan_segments``
pair (CMSIS-NN's observation that per-op overhead, not MACs, dominates
small-layer nets applies to per-node dispatch on TPU just the same).

Three segment shapes exist, all expressed by one :class:`Segment` record:

* **single step** — one branch of length 1: unrolled dispatch (joins,
  heterogeneous layers).
* **stacked chain run** — one branch of length L>1: a sole-consumer run of
  spec-identical steps executes as ``lax.scan`` over weights stacked on a
  new leading axis, with the donated two-bank carry (DESIGN.md §2).
* **batched isomorphic branches** — B>1 branches, pairwise identical specs
  (`repro.core.graph.spec_key`), shapes and views: the branch inputs stack
  on a leading axis and the whole group runs as a *single* scan with a
  batched two-bank carry — per-position weights gain shape ``(L, B, ...)``,
  the carry ``(B, ...)``, and the B outputs split back apart at the join
  (DESIGN.md §8).

Stacked runs are additionally **spec-periodic** (DAG schedules only): a
sole-consumer chain whose specs repeat with period *p* ≥ 2 — the
alternating depthwise/pointwise DS-CNN backbone is the canonical case —
compiles into a *single* ``lax.scan`` of length ``steps/p`` whose body
applies the *p* phase layers in order, with per-phase weights stacked
along the scan axis.  The two-bank carry is unchanged: cross-period
isomorphism guarantees the phase-0 input shape equals the phase-(p-1)
output shape, so the carry stays constant across iterations.  Period 1
is the former homogeneous-run special case.

Segments are pure schedule metadata (names + positions); the executors
supply the numerics, so one partition serves the float and int8 runtimes
alike.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

from repro.core.graph import spec_key

# Bounded-FIFO size for the per-(graph, plan) segment cache below.
_SEGMENT_CACHE_MAX = 64


@dataclasses.dataclass(frozen=True)
class Segment:
    """One executable unit of a schedule.

    ``branches`` holds ≥1 name tuples, all the same length; ``start`` is the
    schedule position of the first covered step (an index into the plan's
    buffer order for DAG schedules, into the materialized-step list for
    sequential graphs).  Branch *b*, position *j* is the step executed at
    schedule position ``start + b·steps_per_branch + j``.

    ``period`` is the spec period of a stacked run: branch position *j* is
    isomorphic to position ``j mod period``, so the run scans
    ``steps_per_branch / period`` iterations whose body applies the
    ``period`` phase layers in order.  ``period == 1`` is the homogeneous
    run (every step isomorphic to the first).
    """

    start: int
    kind: str
    branches: Tuple[Tuple[str, ...], ...]
    period: int = 1

    @property
    def steps_per_branch(self) -> int:
        """Schedule steps covered per branch (= length · period)."""
        return len(self.branches[0])

    @property
    def length(self) -> int:
        """Scan length: iterations of the (period-long) body per branch."""
        return len(self.branches[0]) // self.period

    @property
    def n_branches(self) -> int:
        return len(self.branches)

    @property
    def names(self) -> Tuple[str, ...]:
        """All covered step names, in schedule order."""
        return tuple(n for br in self.branches for n in br)

    @property
    def stacked(self) -> bool:
        """True iff the segment scans over stacked weights (L>1)."""
        return self.length > 1

    @property
    def batched(self) -> bool:
        """True iff the segment batches isomorphic branches (B>1)."""
        return self.n_branches > 1

    @property
    def periodic(self) -> bool:
        """True iff the scan body covers more than one phase layer."""
        return self.period > 1

    @property
    def join(self) -> bool:
        """True iff the segment is one join step (``Add``/``Concat``):
        joins read several values, so they never stack or batch."""
        return self.kind in ("Add", "Concat")

    @property
    def form(self) -> str:
        """How the segment executes: ``batched``, ``periodic-scan``,
        ``scan`` or ``single``."""
        if self.batched:
            return "batched"
        if self.periodic:
            return "periodic-scan"
        if self.stacked:
            return "scan"
        return "single"


def cache_fifo(cache: Dict, key, max_entries: int, build: Callable,
               name: str = ""):
    """Bounded-FIFO memo shared by the segment and executor caches (here,
    `repro.core.pingpong` and `repro.quant.exec`).  The cached value must
    hold strong references to every object whose ``id`` appears in ``key``
    — that is what keeps the id-based keys valid for the entry's
    lifetime.

    A non-empty ``name`` reports ``cache.<name>.hits`` / ``.builds`` /
    ``.evictions`` counters into the process-global
    :data:`repro.obs.metrics.REGISTRY` (one attribute check + dict update
    per call — negligible next to any ``build``).
    """
    metrics = _registry() if name else None
    hit = cache.get(key)
    if hit is None:
        while len(cache) >= max_entries:
            cache.pop(next(iter(cache)))
            if metrics is not None:
                metrics.inc(f"cache.{name}.evictions")
        hit = cache[key] = build()
        if metrics is not None:
            metrics.inc(f"cache.{name}.builds")
    elif metrics is not None:
        metrics.inc(f"cache.{name}.hits")
    return hit


def _registry():
    # Deferred import: obs depends on nothing in core, but importing it at
    # module top would still widen the core import surface unnecessarily.
    from repro.obs.metrics import REGISTRY
    return REGISTRY


# ---------------------------------------------------------------------------
# Step records: the minimal schedule view the compiler needs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _StepView:
    """What the compiler needs to know about one buffer-owning step."""

    name: str
    layer: object
    view_kinds: Tuple[str, ...]
    inputs: Tuple[str, ...]
    in_shapes: Tuple[Tuple[int, ...], ...]
    out_shape: Tuple[int, ...]


def _dag_step_views(mat) -> Dict[str, _StepView]:
    return {
        s.name: _StepView(
            name=s.name,
            layer=s.layer,
            view_kinds=tuple(v.kind for v in s.views),
            inputs=s.inputs,
            in_shapes=s.in_shapes,
            out_shape=s.out_shape,
        )
        for s in mat.steps
    }


def _steps_isomorphic(a: _StepView, b: _StepView) -> bool:
    """True iff two steps are identical up to weights (and input sources)."""
    return (
        spec_key(a.layer) == spec_key(b.layer)
        and a.view_kinds == b.view_kinds
        and a.in_shapes == b.in_shapes
        and a.out_shape == b.out_shape
    )


def _sole_consumer_chains(
    steps: Dict[str, _StepView],
    consumers: Dict[str, Tuple[str, ...]],
    order: Sequence[str],
    first: int,
) -> List[Tuple[int, List[str]]]:
    """Maximal sole-consumer chains over ``order[first:]``.

    A chain extends from step *i* to *i+1* iff step *i+1*'s only input is
    step *i*, which is read by nothing else, and both steps are
    single-input — the structural condition under which a two-bank scan
    carry is valid regardless of specs.  Returns ``(start, names)`` pairs;
    ``start`` indexes ``order``; chains tile the schedule contiguously.
    """
    chains: List[Tuple[int, List[str]]] = []
    i = first
    while i < len(order):
        names = [order[i]]
        head = steps[order[i]]
        while len(head.inputs) == 1:
            j = i + len(names)
            if j >= len(order):
                break
            prev, cur = steps[order[j - 1]], steps[order[j]]
            if len(cur.inputs) != 1 or cur.inputs != (prev.name,):
                break
            if consumers[prev.name] != (cur.name,):
                break
            names.append(cur.name)
        chains.append((i, names))
        i += len(names)
    return chains


def _periodic_factor(
    steps: Dict[str, _StepView], chain: Sequence[str], *, max_period: int
) -> List[Tuple[int, Tuple[str, ...], int]]:
    """Factor one sole-consumer chain into spec-periodic runs.

    Greedy from the left: at each position pick the period *p* (1 ≤ p ≤
    ``max_period``) whose repetition covers the most steps, requiring at
    least two full periods; ties prefer the smallest period, so homogeneous
    runs keep their former period-1 form.  Cross-period isomorphism
    (`_steps_isomorphic`, position-wise) implies the phase-0 input shape
    equals the phase-(p-1) output shape — the constant scan carry.  Steps
    that repeat under no period become single-step runs.  Returns
    ``(offset_in_chain, names, period)`` triples tiling the chain.
    """
    runs: List[Tuple[int, Tuple[str, ...], int]] = []
    n = len(chain)
    i = 0
    while i < n:
        best_p, best_cover = 1, 1
        for p in range(1, min(max_period, (n - i) // 2) + 1):
            reps = 1
            while i + (reps + 1) * p <= n and all(
                _steps_isomorphic(
                    steps[chain[i + j]], steps[chain[i + reps * p + j]]
                )
                for j in range(p)
            ):
                reps += 1
            if reps >= 2 and reps * p > best_cover:
                best_p, best_cover = p, reps * p
        runs.append((i, tuple(chain[i : i + best_cover]), best_p))
        i += best_cover
    return runs


def _chain_runs(
    steps: Dict[str, _StepView],
    consumers: Dict[str, Tuple[str, ...]],
    order: Sequence[str],
    first: int,
    *,
    max_period: int = 1,
) -> List[Tuple[int, Tuple[str, ...], int]]:
    """Maximal stackable runs over ``order[first:]``.

    Sole-consumer chains (`_sole_consumer_chains`) factored into
    spec-periodic runs (`_periodic_factor`).  With ``max_period=1`` this is
    exactly the former homogeneous-run partition; DAG schedules pass a
    larger bound so alternating backbones (DS-CNN's dw/pw) stack too.
    Returns ``(start, names, period)`` triples; ``start`` indexes ``order``.
    """
    runs: List[Tuple[int, Tuple[str, ...], int]] = []
    for start, chain in _sole_consumer_chains(steps, consumers, order, first):
        for off, names, period in _periodic_factor(
            steps, chain, max_period=max_period
        ):
            runs.append((start + off, names, period))
    return runs


def _run_isomorphic(
    steps: Dict[str, _StepView], a: Tuple[str, ...], b: Tuple[str, ...]
) -> bool:
    """True iff two chain runs match position-wise up to weights."""
    if len(a) != len(b):
        return False
    return all(_steps_isomorphic(steps[x], steps[y]) for x, y in zip(a, b))


def _batchable(steps: Dict[str, _StepView], names: Tuple[str, ...]) -> bool:
    """Only single-input steps batch (a join's input list cannot stack)."""
    return all(len(steps[n].inputs) == 1 for n in names)


def _group_segments(
    steps: Dict[str, _StepView],
    runs: List[Tuple[int, Tuple[str, ...], int]],
    *,
    batch_branches: bool,
) -> Tuple[Segment, ...]:
    """Fold adjacent isomorphic, mutually independent runs into one Segment.

    Runs tile the schedule contiguously, so adjacency in the run list is
    adjacency in the schedule; a candidate branch joins the group iff it has
    the same period, matches position-wise, and its (single) input step lies
    outside the group — i.e. it was produced before the group's start —
    which makes the branches executable simultaneously.
    """
    segs: List[Segment] = []
    i = 0
    while i < len(runs):
        start, names, period = runs[i]
        group = [names]
        j = i + 1
        if batch_branches and _batchable(steps, names):
            covered = set(names)
            while j < len(runs):
                _, cand, cand_period = runs[j]
                if cand_period != period:
                    break
                if not _batchable(steps, cand):
                    break
                if not _run_isomorphic(steps, names, cand):
                    break
                if steps[cand[0]].inputs[0] in covered:
                    break  # reads a value produced inside the group
                group.append(cand)
                covered.update(cand)
                j += 1
        segs.append(
            Segment(
                start=start,
                kind=steps[names[0]].layer.kind,
                branches=tuple(group),
                period=period,
            )
        )
        i = j if len(group) > 1 else i + 1
    return tuple(segs)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


# Largest spec period the run factorization searches for.  2 covers the
# depthwise/pointwise alternation (DS-CNN, MobileNet-style backbones); a
# few more cost nothing on these graph sizes and catch e.g. dw/pw/pool
# triples, so the bound is small but not minimal.
_MAX_PERIOD = 4


def compile_segments(mat, order: Sequence[str], *, batch_branches: bool = True):
    """Compile a scheduled DAG into segments.

    ``mat`` is a `repro.core.schedule.MaterializedDAG`; ``order`` the plan's
    schedule (``order[0]`` is the input step, which owns no segment).  With
    ``batch_branches=False`` only chain stacking applies — the per-branch
    dispatch baseline the benchmarks compare against.  Chain runs are
    spec-periodic up to period ``_MAX_PERIOD``.
    """
    steps = _dag_step_views(mat)
    runs = _chain_runs(
        steps, mat.consumers(), tuple(order), 1, max_period=_MAX_PERIOD
    )
    return _group_segments(steps, runs, batch_branches=batch_branches)


def sequential_segments(graph) -> Tuple[Segment, ...]:
    """Compile a sequential graph's materialized steps into segments.

    The sequential executor's view of the same partition: step *i* is the
    *i*-th materialized layer (``MemoryPlan.buffers[i+1]``), names are layer
    names, and there are no branches to batch — segments are single steps
    and stacked chain runs only.
    """
    from repro.core.planner import materialized_steps

    _, steps = materialized_steps(graph)
    views: Dict[str, _StepView] = {}
    order: List[str] = []
    for i, (layer, view_layers, in_shape, out_shape) in enumerate(steps):
        # Positional names keep duplicate layer names distinct here; the
        # executor maps positions back to layer names for the param lookup.
        name = f"#{i}:{layer.name or layer.kind}"
        prev = order[-1] if order else "#input"
        views[name] = _StepView(
            name=name,
            layer=layer,
            view_kinds=tuple(v.kind for v in view_layers),
            inputs=(prev,),
            in_shapes=(tuple(in_shape),),
            out_shape=tuple(out_shape),
        )
        order.append(name)
    consumers = {
        name: (order[i + 1],) if i + 1 < len(order) else ()
        for i, name in enumerate(order)
    }
    # max_period stays 1 here: `planner.scan_segments` (StackedRun) promises
    # homogeneous runs, and the sequential nets have no alternating backbone.
    runs = _chain_runs(views, consumers, order, 0, max_period=1)
    segs = _group_segments(views, runs, batch_branches=False)
    # Strip the positional prefix: report plain layer names, like the plans.
    return tuple(
        Segment(
            start=s.start,
            kind=s.kind,
            branches=tuple(
                tuple(n.split(":", 1)[1] for n in br) for br in s.branches
            ),
            period=s.period,
        )
        for s in segs
    )


# Keyed by object identity (+ the batching flag); values keep the graph and
# plan alive so the ids stay valid.  This is the cache that deduplicates the
# segment computation between executor construction and stats reporting.
_SEGMENT_CACHE: Dict[Tuple[int, int, bool], tuple] = {}


def segments_for_plan(graph, plan, *, batch_branches: bool = True):
    """``(materialized, order, segments)`` for a (DAG graph, plan) pair.

    Validates the plan against the graph (`schedule.check_dag_plan`) and
    compiles its schedule once per (graph, plan, batch_branches) triple —
    every consumer (executor builders, stats, benchmarks) shares the cached
    result.
    """
    from repro.core.schedule import check_dag_plan

    def build():
        mat, order = check_dag_plan(graph, plan)
        segs = compile_segments(mat, order, batch_branches=batch_branches)
        return (graph, plan, mat, order, segs)

    hit = cache_fifo(
        _SEGMENT_CACHE,
        (id(graph), id(plan), batch_branches),
        _SEGMENT_CACHE_MAX,
        build,
        name="segments",
    )
    return hit[2], hit[3], hit[4]


def segment_stats(segments: Sequence[Segment]) -> Dict[str, int]:
    """Executor-stats summary of a segment partition."""
    return {
        "segments": len(segments),
        "stacked_layers": sum(
            s.steps_per_branch * s.n_branches
            for s in segments
            if s.stacked or s.batched
        ),
        "batched_branches": sum(s.n_branches for s in segments if s.batched),
        "periodic_segments": sum(1 for s in segments if s.periodic),
        "periodic_steps": sum(
            s.steps_per_branch * s.n_branches for s in segments if s.periodic
        ),
        "join_steps": sum(1 for s in segments if s.join),
    }
