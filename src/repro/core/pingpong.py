"""Arena executors: run a sequential graph *inside the planned arena*.

Two executors share the plan-validation logic:

* :func:`run_with_arena` — the Python-loop walker.  Every inter-layer tensor
  is placed at its planned offset in one flat arena array, one eager dispatch
  per layer and per ``dynamic_slice``.  It is deliberately unjitted: the
  *slow oracle* that proves the plan correct (if two live buffers overlapped,
  the output would diverge from :func:`repro.core.nn.forward`).

* :func:`run_with_arena_scan` — the compiled executor (DESIGN.md §2).  The
  whole network traces into **one** XLA program: homogeneous layer runs
  (``repro.core.segments``, the segment compiler) execute as ``lax.scan``
  over stacked weights with a two-bank carry ``(cur, prev)``.  Each step writes the bank
  the step before read from — with buffer donation XLA aliases the two carry
  slots onto two alternating HBM buffers, which *is* the paper's §3.2
  ping-pong discipline realized on TPU.  ``run_batch_with_arena`` pushes N
  images through the same plan in one call (the banks gain a leading batch
  dimension; the alternation is unchanged).

Offsets and shapes are trace-time constants taken from the plan, so the
compiled executor re-dispatches neither per layer nor per slice.

Both executors are parametric in ``apply_layer_fn(layer, params, x)`` — the
per-layer numerics.  The default is the float oracle semantics
(:func:`repro.core.nn.apply_layer`); the int8 runtime (``repro.quant.exec``)
passes its q7-style int8 step instead and inherits the arena bookkeeping,
segment grouping and two-bank carry unchanged (DESIGN.md §6).  The arena
dtype follows the input's dtype, so an int8 input yields a genuine int8
arena.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import schedule as schedule_mod
from repro.core import segments as segments_mod
from repro.core.graph import DAGGraph, Input, SequentialGraph, as_sequential
from repro.core.nn import Params, apply_layer, apply_node
from repro.core.planner import MemoryPlan, materialized_steps
from repro.core.segments import cache_fifo  # shared bounded-FIFO memo

# Backends where jit buffer donation is implemented; elsewhere donating only
# produces a warning, so we skip it.
_DONATING_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

# Compiled executors kept per (graph, plan) object pair, bounded FIFO.
_EXEC_CACHE_MAX = 32


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def check_plan(graph: SequentialGraph, plan: MemoryPlan):
    """Shared walker/scan validation: plan buffers line up 1:1 with the
    graph's materialized layers.  Returns the materialized rows."""
    graph = as_sequential(graph, caller="pingpong.check_plan")
    rows = [l for l in graph.layers if l.kind not in ("ReLU", "Flatten")]
    if len(rows) != len(plan.buffers):
        raise ValueError(
            f"plan has {len(plan.buffers)} buffers but graph materializes "
            f"{len(rows)} — fuse the graph with the same options as the plan"
        )
    return rows


def run_with_arena(
    graph: SequentialGraph,
    plan: MemoryPlan,
    params: Params,
    x: jax.Array,
    *,
    apply_layer_fn=apply_layer,
) -> Tuple[jax.Array, Dict[str, int]]:
    """Execute ``graph`` storing every materialized buffer in the plan arena.

    Returns (output, stats).  ``stats['arena_elems']`` is the peak memory the
    execution actually used — by construction equal to the plan's arena size.

    The graph must be in the same (fused / unfused) form the plan was built
    from, so that materialized layers line up 1:1 with plan buffers.  The
    arena takes ``x``'s dtype; ``apply_layer_fn`` supplies the per-layer
    numerics (default: the float oracle).
    """
    graph = as_sequential(graph, caller="pingpong.run_with_arena")
    check_plan(graph, plan)

    arena = jnp.zeros((plan.arena_elems,), dtype=x.dtype)

    # Place the input at its planned offset.
    in_buf = plan.buffers[0]
    if _prod(x.shape) != in_buf.size_elems:
        raise ValueError(f"input size {x.shape} != planned {in_buf.size_elems}")
    arena = jax.lax.dynamic_update_slice(arena, x.reshape(-1), (in_buf.offset_elems,))

    shapes = graph.shapes()
    cur_shape = x.shape
    buf_idx = 0
    # Walk layers; view layers (ReLU/Flatten standalone) operate on the
    # current buffer in place — exactly as the paper folds them.
    for layer, out_shape in zip(graph.layers, shapes):
        name = layer.name or layer.kind
        if isinstance(layer, Input):
            cur_shape = out_shape
            continue
        src = plan.buffers[buf_idx]
        cur = jax.lax.dynamic_slice(arena, (src.offset_elems,), (src.size_elems,))
        cur = cur.reshape(cur_shape)
        if layer.kind in ("ReLU", "Flatten"):
            out = apply_layer_fn(layer, {}, cur)
            arena = jax.lax.dynamic_update_slice(
                arena, out.reshape(-1), (src.offset_elems,)
            )
            cur_shape = out.shape
            continue
        out = apply_layer_fn(layer, params.get(name, {}), cur)
        buf_idx += 1
        dst = plan.buffers[buf_idx]
        if _prod(out.shape) != dst.size_elems:
            raise ValueError(
                f"layer {name}: produced {out.shape} but plan expects "
                f"{dst.size_elems} elements"
            )
        arena = jax.lax.dynamic_update_slice(
            arena, out.reshape(-1), (dst.offset_elems,)
        )
        cur_shape = out.shape

    final = plan.buffers[-1]
    out = jax.lax.dynamic_slice(arena, (final.offset_elems,), (final.size_elems,))
    stats = {"arena_elems": int(plan.arena_elems), "buffers": len(plan.buffers)}
    return out.reshape(shapes[-1]), stats


# ---------------------------------------------------------------------------
# Compiled scan executor
# ---------------------------------------------------------------------------


def _apply_step(layer, views, p, x, apply_layer_fn=apply_layer):
    out = apply_layer_fn(layer, p, x)
    for v in views:
        out = apply_layer_fn(v, {}, out)
    return out


def _shard_jit(fn, data_parallel, donate: bool):
    """Jit an executor under a DataParallelPolicy's batch sharding.

    Weights replicate (``P()``), the input and output batch axes shard over
    the mesh's ``'data'`` axis; GSPMD propagates the batch sharding through
    the scan carry, so each device runs the whole two-bank arena over its
    batch shard (DESIGN.md §12).  The input batch must divide by the mesh
    size — callers pad remainders (``DataParallelPolicy.wrap_batched`` /
    the serving bucket ladder's rounded buckets)."""
    repl = data_parallel.replicated()
    batch = data_parallel.batch_sharding()
    return jax.jit(fn, in_shardings=(repl, batch), out_shardings=batch,
                   donate_argnums=(1,) if donate else ())


def make_scan_executor(
    graph: SequentialGraph,
    plan: MemoryPlan,
    *,
    donate_input: bool = False,
    apply_layer_fn=apply_layer,
    data_parallel=None,
) -> Callable[[Params, jax.Array], jax.Array]:
    """Build the jitted executor for (graph, plan).

    The returned callable maps ``(params, x) -> y`` where ``x`` is one image
    (``in_shape``) or a batch (``(N, *in_shape)``); everything else — layer
    sequence, segment grouping, bank sizes — is baked in as trace-time
    constants.  Reuse the callable across calls to hit jit's cache.

    ``donate_input=True`` additionally donates ``x`` (the bank the input
    occupies) on backends that implement donation — opt-in, because the
    caller's array is deleted and must not be reused afterwards.  The scan
    carries themselves are donated/aliased by XLA inside the compiled
    program regardless.

    ``apply_layer_fn`` supplies the per-layer numerics (default: the float
    oracle; the int8 runtime passes its requantizing step).

    ``data_parallel`` (a ``repro.sharding.policy.DataParallelPolicy``)
    shards the batch axis over the policy's device mesh: weights replicate,
    the input must then be batched with ``N`` a multiple of the mesh size
    (pad remainders via ``DataParallelPolicy.wrap_batched``).  Sharded
    output is bit-exact against the unsharded executor — rows are
    independent, so partitioning the batch inserts no collectives.
    """
    graph = as_sequential(graph, caller="pingpong.make_scan_executor")
    check_plan(graph, plan)
    segments = segments_mod.sequential_segments(graph)
    pre_views, steps = materialized_steps(graph)
    in_shape = tuple(graph.shapes()[0])
    in_elems = _prod(in_shape)
    # The plan's per-buffer sizes, checked against layer outputs at trace time.
    sizes = [b.size_elems for b in plan.buffers]
    if in_elems != sizes[0]:
        raise ValueError(f"input size {in_shape} != planned {sizes[0]}")

    def _exec(params: Params, x: jax.Array) -> jax.Array:
        nbatch = x.ndim - len(in_shape)
        if nbatch not in (0, 1):
            raise ValueError(f"input shape {x.shape} does not match {in_shape}")
        if data_parallel is not None and nbatch != 1:
            raise ValueError(
                f"data-parallel executor requires a batched input "
                f"(N, {in_shape}), got {x.shape}"
            )
        if _prod(x.shape[nbatch:]) != in_elems:
            raise ValueError(f"input size {x.shape} != planned {sizes[0]}")
        cur = x
        for v in pre_views:
            cur = apply_layer_fn(v, {}, cur)
        for seg in segments:
            names = seg.branches[0]
            first_layer, first_views = steps[seg.start][0], steps[seg.start][1]
            if not seg.stacked:
                name = first_layer.name or first_layer.kind
                cur = _apply_step(first_layer, first_views, params.get(name, {}),
                                  cur, apply_layer_fn)
            else:
                # lax.scan over stacked weights; two-bank carry (cur, prev):
                # each step's output may reuse (alias) the bank its input's
                # producer freed — the donated ping-pong pair.
                stacked = jax.tree.map(
                    lambda *leaves: jnp.stack(leaves),
                    *[params.get(n, {}) for n in names],
                )

                def body(carry, p, _layer=first_layer, _views=first_views):
                    bank_cur, bank_prev = carry
                    del bank_prev  # freed: the slot this step's output lands in
                    out = _apply_step(_layer, _views, p, bank_cur, apply_layer_fn)
                    return (out, bank_cur), None

                # length: stacked may be a leafless pytree (parameterless run)
                (cur, _), _ = jax.lax.scan(body, (cur, cur), stacked,
                                           length=seg.length)
            # buffers[0] is the input, so step i writes plan buffer i+1.
            if _prod(cur.shape[nbatch:]) != sizes[seg.start + seg.steps_per_branch]:
                raise ValueError(
                    f"segment {names}: produced {cur.shape} but plan "
                    f"expects {sizes[seg.start + seg.steps_per_branch]} elements"
                )
        return cur

    donate = donate_input and jax.default_backend() in _DONATING_BACKENDS
    if data_parallel is not None:
        return _shard_jit(_exec, data_parallel, donate)
    return jax.jit(_exec, donate_argnums=(1,) if donate else ())


# Keyed by object identity; values keep the graph/plan alive so ids stay
# valid.  Bounded FIFO: the convenience wrappers only ever see a handful of
# (graph, plan) pairs per process; heavy users should hold their own
# make_scan_executor result instead.
_EXEC_CACHE: Dict[
    Tuple[int, int], Tuple[SequentialGraph, MemoryPlan, Callable, Dict[str, int]]
] = {}


def _cached_executor(graph: SequentialGraph, plan: MemoryPlan):
    """(executor, stats) for (graph, plan), computed once per pair."""

    def build():
        segments = segments_mod.sequential_segments(graph)
        stats = {
            "arena_elems": int(plan.arena_elems),
            "buffers": len(plan.buffers),
            **segments_mod.segment_stats(segments),
        }
        return (graph, plan, make_scan_executor(graph, plan), stats)

    hit = cache_fifo(_EXEC_CACHE, (id(graph), id(plan)), _EXEC_CACHE_MAX, build,
                     name="scan_exec")
    return hit[2], hit[3]


def run_with_arena_scan(
    graph: SequentialGraph,
    plan: MemoryPlan,
    params: Params,
    x: jax.Array,
) -> Tuple[jax.Array, Dict[str, int]]:
    """Compiled counterpart of :func:`run_with_arena` (same signature).

    Returns (output, stats); ``stats`` additionally reports the homogeneous
    segment grouping.  Byte-exact against the walker — both run the same
    layer numerics, only the dispatch differs.
    """
    fn, stats = _cached_executor(graph, plan)
    return fn(params, x), dict(stats)


def run_batch_with_arena(
    graph: SequentialGraph,
    plan: MemoryPlan,
    params: Params,
    xs: jax.Array,  # (N, *in_shape)
) -> Tuple[jax.Array, Dict[str, int]]:
    """N images through one arena plan in a single compiled dispatch.

    The ping-pong banks simply gain a leading batch dimension (arena cost is
    ``N · arena_elems``); the bank alternation is identical per image.
    """
    in_ndim = len(graph.shapes()[0])
    if xs.ndim != in_ndim + 1:
        raise ValueError(f"expected batched input (N, ...), got {xs.shape}")
    fn, stats = _cached_executor(graph, plan)
    out = fn(params, xs)
    stats = dict(stats)
    stats["batch"] = int(xs.shape[0])
    return out, stats


# ---------------------------------------------------------------------------
# DAG executors (reordered schedules from repro.core.schedule)
# ---------------------------------------------------------------------------


# Shared walker/scan/emitter validation of (graph, plan) schedule pairs.
check_dag_plan = schedule_mod.check_dag_plan


def run_dag_with_arena(
    graph: DAGGraph,
    plan: MemoryPlan,
    params: Params,
    x: jax.Array,
    *,
    apply_node_fn=apply_node,
) -> Tuple[jax.Array, Dict[str, int]]:
    """Execute a DAG inside the planned arena, in the plan's schedule order.

    The DAG counterpart of :func:`run_with_arena`: every materialized buffer
    lives at its planned offset in one flat arena, one eager dispatch per
    step.  Deliberately unjitted — the slow oracle proving the reordered
    schedule's offsets clobber-free (a bad interval assignment would diverge
    from :func:`repro.core.nn.forward_dag`).

    ``apply_node_fn(layer, p, xs)`` supplies the numerics (default: the
    float oracle; the int8 runtime passes its requantizing node step).
    """
    mat, order = check_dag_plan(graph, plan)
    steps = {s.name: s for s in mat.steps}
    bufs = {b.name: b for b in plan.buffers}

    arena = jnp.zeros((plan.arena_elems,), dtype=x.dtype)

    in_step = steps[order[0]]
    in_buf = bufs[order[0]]
    if _prod(x.shape) != in_buf.size_elems:
        raise ValueError(f"input size {x.shape} != planned {in_buf.size_elems}")
    val = x
    for v in in_step.views:
        val = apply_node_fn(v, {}, [val])
    arena = jax.lax.dynamic_update_slice(arena, val.reshape(-1), (in_buf.offset_elems,))

    for name in order[1:]:
        step = steps[name]
        xs = []
        for src in step.inputs:
            sb = bufs[src]
            v = jax.lax.dynamic_slice(arena, (sb.offset_elems,), (sb.size_elems,))
            xs.append(v.reshape(steps[src].out_shape))
        out = apply_node_fn(step.layer, params.get(name, {}), xs)
        for v in step.views:
            out = apply_node_fn(v, {}, [out])
        dst = bufs[name]
        if _prod(out.shape) != dst.size_elems:
            raise ValueError(
                f"step {name}: produced {out.shape} but plan expects "
                f"{dst.size_elems} elements"
            )
        arena = jax.lax.dynamic_update_slice(
            arena, out.reshape(-1), (dst.offset_elems,)
        )

    final = bufs[mat.output]
    out = jax.lax.dynamic_slice(arena, (final.offset_elems,), (final.size_elems,))
    stats = {"arena_elems": int(plan.arena_elems), "buffers": len(plan.buffers)}
    return out.reshape(steps[mat.output].out_shape), stats


def _apply_step_views(step, p, xs, apply_node_fn):
    out = apply_node_fn(step.layer, p, xs)
    for v in step.views:
        out = apply_node_fn(v, {}, [out])
    return out


def _stack_params(params, names):
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params.get(n, {}) for n in names],
    )


def apply_dag_segment(
    steps,
    sizes,
    seg,
    params: Params,
    vals: Dict[str, jax.Array],
    nbatch: int,
    *,
    index: int,
    apply_node_fn=apply_node,
) -> Dict[str, jax.Array]:
    """Execute one compiled segment and return its tail values.

    The single-segment unit of :func:`make_dag_executor`'s traced loop,
    exposed so `obs/report.py` can jit *one segment at a time* for the
    per-segment device-timing mode without duplicating the lowering logic.
    ``steps`` maps step name → :class:`~repro.core.schedule.Step`, ``sizes``
    maps buffer name → planned element count, ``vals`` holds the live
    buffer values the segment reads; the returned dict maps each branch
    tail to its produced value.  ``index`` is the segment's position in
    the schedule: its ops run under ``jax.named_scope("seg<index>.<form>")``
    so the compiled program names every segment, and a join step's ops under
    a nested ``join.<node>`` scope (``seg<index>.single/join.<node>``).
    """
    with jax.named_scope(f"seg{index}.{seg.form}"):
        first = steps[seg.branches[0][0]]
        # The scan body applies the segment's `period` phase layers in
        # order (period 1: the homogeneous run).  Phase j's weights for
        # iteration k come from branch position k·period + j, so the
        # per-phase stack along the scan axis is names[j::period].
        phases = [steps[n] for n in seg.branches[0][: seg.period]]
        _apply = lambda step, p, xs: _apply_step_views(step, p, xs, apply_node_fn)
        if seg.batched:
            # Batched isomorphic branches: stack the B branch inputs on a
            # new leading axis and run the whole group as one dispatch
            # (L = 1) or one lax.scan with a batched two-bank carry
            # (L > 1; the chain-run invariants guarantee a constant
            # carry shape).  Weights stack to (L, B, ...) per phase.
            xs = jnp.stack(
                [vals[steps[br[0]].inputs[0]] for br in seg.branches]
            )
            if seg.length == 1:
                per_branch = _stack_params(
                    params, [br[0] for br in seg.branches]
                )
                ys = jax.vmap(
                    lambda p, xx, _step=first: _apply(_step, p, [xx])
                )(per_branch, xs)
            else:
                stacked = tuple(
                    jax.tree.map(
                        lambda *leaves: jnp.stack(leaves),
                        *[
                            _stack_params(
                                params,
                                [br[k * seg.period + j] for br in seg.branches],
                            )
                            for k in range(seg.length)
                        ],
                    )
                    for j in range(seg.period)
                )

                def body(carry, ps, _phases=phases):
                    bank_cur, bank_prev = carry
                    del bank_prev  # freed: this step's output lands there
                    out = bank_cur
                    for step, p in zip(_phases, ps):
                        out = jax.vmap(
                            lambda pp, xx, _step=step: _apply(_step, pp, [xx])
                        )(p, out)
                    return (out, bank_cur), None

                (ys, _), _ = jax.lax.scan(body, (xs, xs), stacked,
                                          length=seg.length)
            out_vals: Dict[str, jax.Array] = {}
            for k, br in enumerate(seg.branches):
                tail = br[-1]
                if _prod(ys.shape[1 + nbatch:]) != sizes[tail]:
                    raise ValueError(
                        f"segment {seg.branches}: produced {ys.shape} but "
                        f"plan expects {sizes[tail]} elements"
                    )
                out_vals[tail] = ys[k]
            return out_vals
        names = seg.branches[0]
        if len(names) == 1:
            xs = [vals[src] for src in first.inputs]
            with (jax.named_scope(f"join.{first.name}") if seg.join
                  else contextlib.nullcontext()):
                cur = _apply(first, params.get(first.name, {}), xs)
        else:
            cur = vals[first.inputs[0]]
            stacked = tuple(
                _stack_params(params, names[j :: seg.period])
                for j in range(seg.period)
            )

            def body(carry, ps, _phases=phases):
                bank_cur, bank_prev = carry
                del bank_prev  # freed: this step's output lands there
                out = bank_cur
                for step, p in zip(_phases, ps):
                    out = _apply(step, p, [out])
                return (out, bank_cur), None

            (cur, _), _ = jax.lax.scan(body, (cur, cur), stacked,
                                       length=seg.length)
        if _prod(cur.shape[nbatch:]) != sizes[names[-1]]:
            raise ValueError(
                f"segment {names}: produced {cur.shape} but plan expects "
                f"{sizes[names[-1]]} elements"
            )
        return {names[-1]: cur}


def make_dag_executor(
    graph: DAGGraph,
    plan: MemoryPlan,
    *,
    donate_input: bool = False,
    apply_node_fn=apply_node,
    batch_branches: bool = True,
    data_parallel=None,
) -> Callable[[Params, jax.Array], jax.Array]:
    """Build the jitted DAG executor for (graph, plan).

    The whole schedule traces into **one** XLA program, steps in the plan's
    (reordered) order, partitioned by the segment compiler
    (`repro.core.segments`): sole-consumer homogeneous chain runs execute as
    ``lax.scan`` over stacked weights with the donated two-bank carry, just
    like the sequential scan executor, and **isomorphic branches** (specs
    identical up to weights) execute as a *single* scan with a batched
    two-bank carry — branch inputs stacked on a leading axis, per-position
    weights stacked ``(L, B, ...)``, outputs split back apart at the join.
    **Spec-periodic** chain runs (period p ≥ 2, e.g. the alternating dw/pw
    DS-CNN backbone) scan ``steps/p`` iterations whose body applies the p
    phase layers in order, with per-phase weights stacked along the scan
    axis — the same two-bank carry, one scan for the whole backbone.
    Join nodes and heterogeneous steps are unrolled.  Accepts one input
    (``in_shape``) or a batch (``(N, *in_shape)``).

    ``batch_branches=False`` disables the isomorphic-branch batching — the
    per-branch dispatch baseline the benchmarks compare against.

    ``data_parallel`` shards the batch axis over a device mesh exactly as in
    :func:`make_scan_executor`: weights replicated, input batched with ``N``
    a multiple of the mesh size, output bit-exact vs unsharded.
    """
    mat, order, segments = segments_mod.segments_for_plan(
        graph, plan, batch_branches=batch_branches
    )
    steps = {s.name: s for s in mat.steps}
    in_shape = tuple(graph.nodes[0].layer.shape)
    in_elems = _prod(in_shape)
    sizes = {b.name: b.size_elems for b in plan.buffers}

    def _exec(params: Params, x: jax.Array) -> jax.Array:
        nbatch = x.ndim - len(in_shape)
        if nbatch not in (0, 1):
            raise ValueError(f"input shape {x.shape} does not match {in_shape}")
        if data_parallel is not None and nbatch != 1:
            raise ValueError(
                f"data-parallel executor requires a batched input "
                f"(N, {in_shape}), got {x.shape}"
            )
        if _prod(x.shape[nbatch:]) != in_elems:
            raise ValueError(f"input size {x.shape} != planned {in_elems}")
        val = x
        for v in steps[order[0]].views:
            val = apply_node_fn(v, {}, [val])
        vals: Dict[str, jax.Array] = {order[0]: val}
        for i, seg in enumerate(segments):
            vals.update(apply_dag_segment(
                steps, sizes, seg, params, vals, nbatch, index=i,
                apply_node_fn=apply_node_fn,
            ))
        return vals[mat.output]

    donate = donate_input and jax.default_backend() in _DONATING_BACKENDS
    if data_parallel is not None:
        return _shard_jit(_exec, data_parallel, donate)
    return jax.jit(_exec, donate_argnums=(1,) if donate else ())


# Keyed by object identity; values keep the graph/plan alive so ids stay valid.
_DAG_EXEC_CACHE: Dict[
    Tuple[int, int], Tuple[DAGGraph, MemoryPlan, Callable, Dict[str, int]]
] = {}


def _cached_dag_executor(graph: DAGGraph, plan: MemoryPlan):
    def build():
        # The segment cache makes this the same compilation the executor
        # builder uses — computed once per (graph, plan) pair.
        _, _, segments = segments_mod.segments_for_plan(graph, plan)
        stats = {
            "arena_elems": int(plan.arena_elems),
            "buffers": len(plan.buffers),
            **segments_mod.segment_stats(segments),
        }
        return (graph, plan, make_dag_executor(graph, plan), stats)

    hit = cache_fifo(_DAG_EXEC_CACHE, (id(graph), id(plan)), _EXEC_CACHE_MAX,
                     build, name="dag_exec")
    return hit[2], hit[3]


def run_dag_with_arena_scan(
    graph: DAGGraph,
    plan: MemoryPlan,
    params: Params,
    x: jax.Array,
) -> Tuple[jax.Array, Dict[str, int]]:
    """Compiled counterpart of :func:`run_dag_with_arena` (same signature).

    Bit-exact against the walker — same numerics, different bookkeeping."""
    fn, stats = _cached_dag_executor(graph, plan)
    return fn(params, x), dict(stats)


def run_batch_dag_with_arena(
    graph: DAGGraph,
    plan: MemoryPlan,
    params: Params,
    xs: jax.Array,  # (N, *in_shape)
) -> Tuple[jax.Array, Dict[str, int]]:
    """N images through one reordered DAG plan in a single compiled dispatch."""
    in_ndim = len(graph.nodes[0].layer.shape)
    if xs.ndim != in_ndim + 1:
        raise ValueError(f"expected batched input (N, ...), got {xs.shape}")
    fn, stats = _cached_dag_executor(graph, plan)
    out = fn(params, xs)
    stats = dict(stats)
    stats["batch"] = int(xs.shape[0])
    return out, stats


# ---------------------------------------------------------------------------
# Ahead-of-time lowering (serving entry point)
# ---------------------------------------------------------------------------


def aot_compile(fn: Callable, params: Params, x_shape, dtype):
    """AOT ``.lower().compile()`` of a jitted executor at a fixed input shape.

    ``fn`` is any ``(params, x) -> y`` executor built by
    :func:`make_scan_executor` or :func:`make_dag_executor` (float or int8 —
    the numerics travel in ``apply_*_fn`` and ``params``).  Lowering against
    ``jax.ShapeDtypeStruct`` specs compiles the XLA program *now*, so a
    serving replica pays first-call jit cost at deploy time instead of on
    the first request — the cold-start half of the ROADMAP's AOT item.  The
    returned ``jax.stages.Compiled`` accepts exactly ``(params, x)`` with
    ``x.shape == x_shape``; the serving engine keeps one per batch bucket.
    """
    p_spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)), params
    )
    x_spec = jax.ShapeDtypeStruct(tuple(x_shape), dtype)
    return fn.lower(p_spec, x_spec).compile()
