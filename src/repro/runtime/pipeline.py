"""Pipeline parallelism over a mesh axis (GPipe-schedule via shard_map +
ppermute), DESIGN.md §5.

The paper's C4 module-level multithreading — independent compute modules
working on different inputs concurrently — is exactly a pipeline; at pod
scale the stages map onto the "pod" axis so the only cross-pod (DCN-class)
traffic is one microbatch activation per tick instead of full gradient
all-reduces.

Schedule: M microbatches through S stages in M + S - 1 ticks; every tick
each stage runs its block stack on the activation it holds, then the ring
ppermute shifts activations one stage forward.  jax.grad through the loop
replays it in reverse (ppermute transposes to the inverse permutation),
giving the backward pipeline for free; per-stage remat keeps the
activation footprint at O(M) boundary tensors instead of O(M*L_stage).
"""
from __future__ import annotations

import functools
import queue
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

F32 = jnp.float32


class HostPipeline:
    """The paper's C4 module-level multithreading on host: a chain of
    stages connected by bounded queues, one thread per stage, so stage i
    of item n overlaps stage i+1 of item n-1 (serve.py's preprocess /
    device-infer / CC-postprocess chain is the motivating instance).

    ``stages`` are ``fn(item) -> item``; ``run`` preserves input order.
    A stage exception propagates to the caller and stops the pipeline.
    """

    def __init__(self, stages: Sequence[Callable[[Any], Any]],
                 maxsize: int = 4):
        if not stages:
            raise ValueError("need at least one stage")
        self.stages = list(stages)
        self.maxsize = maxsize

    def run(self, items: Sequence[Any]) -> List[Any]:
        n_stages = len(self.stages)
        qs = [queue.Queue(maxsize=self.maxsize) for _ in range(n_stages + 1)]
        results: List[Any] = [None] * len(items)
        errors: List[BaseException] = []
        abort = threading.Event()        # a stage error must unwind EVERY
                                         # thread, not just downstream ones

        def _put(q, item) -> bool:
            while not abort.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def _get(q):
            """Item, or None sentinel, or False once aborted+drained."""
            while True:
                try:
                    return q.get(timeout=0.05)
                except queue.Empty:
                    if abort.is_set():
                        return False

        def feeder():
            for i, item in enumerate(items):
                if not _put(qs[0], (i, item)):
                    return
            _put(qs[0], None)

        def worker(si: int):
            fn = self.stages[si]
            while True:
                got = _get(qs[si])
                if got is False:
                    return
                if got is None:
                    _put(qs[si + 1], None)
                    return
                i, item = got
                try:
                    out = fn(item)
                except Exception as e:
                    errors.append(e)
                    abort.set()
                    return
                if not _put(qs[si + 1], (i, out)):
                    return

        def sink():
            while True:
                got = _get(qs[n_stages])
                if got is False or got is None:
                    return
                i, item = got
                results[i] = item

        threads = [threading.Thread(target=feeder, daemon=True)]
        threads += [
            threading.Thread(target=worker, args=(si,), daemon=True)
            for si in range(n_stages)
        ]
        threads.append(threading.Thread(target=sink, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return results


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L/S, ...)."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers % {n_stages} stages"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)


def pipeline_apply(
    mesh: Mesh,
    stage_axis: str,
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    staged_params: Any,             # (S, L/S, ...) leaves, sharded dim0
    x: jax.Array,                   # (M, mb, seq, d) microbatched input
    *,
    remat: bool = True,
) -> jax.Array:
    """Run the stage-stacked layer scan as a pipeline; returns (M, mb, s, d).

    ``layer_fn(params_one_layer, h) -> h`` is scanned over the local
    stage's layers; activations ring-shift along `stage_axis`.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
    M = x.shape[0]

    def stage_fn(local_params, h):
        def body(c, lp):
            return layer_fn(lp, c), None
        f = jax.checkpoint(
            lambda c, lp: (layer_fn(lp, c), None)
        ) if remat else body
        out, _ = jax.lax.scan(f, h, local_params)
        return out

    perm_fwd = [(i, (i + 1) % S) for i in range(S)]

    def shard_body(local_params, xm):
        # local_params: (1, L/S, ...) on each stage; xm: (M, mb, s, d) full
        lp = jax.tree_util.tree_map(lambda a: a[0], local_params)
        idx = jax.lax.axis_index(stage_axis)
        mb_shape = xm.shape[1:]
        buf = jnp.zeros(mb_shape, xm.dtype)          # activation in flight
        outs = jnp.zeros((M,) + mb_shape, xm.dtype)
        n_ticks = M + S - 1

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (while t < M); others use the
            # ring buffer
            mb_idx = jnp.minimum(t, M - 1)
            inject = jnp.logical_and(idx == 0, t < M)
            h_in = jnp.where(inject, xm[mb_idx], buf)
            h_out = stage_fn(lp, h_in)
            # last stage emits microbatch t - (S - 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = jnp.logical_and(idx == S - 1, t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(emit, h_out, outs[out_idx]),
                out_idx, axis=0,
            )
            buf = jax.lax.ppermute(h_out, stage_axis, perm_fwd)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # broadcast the last stage's outputs to all stages so the head can
        # be computed data-parallel afterwards
        if S > 1:
            outs = jax.lax.all_gather(outs, stage_axis)[S - 1]
        return outs

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(stage_axis), staged_params),
        P(),
    )
    fn = jax.shard_map(
        shard_body, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    return fn(staged_params, x)


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    B = x.shape[0]
    assert B % n_micro == 0
    return x.reshape(n_micro, B // n_micro, *x.shape[1:])
