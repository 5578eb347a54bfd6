"""Collective ops (ref: ``python/paddle/distributed/communication/`` —
all_reduce, all_gather, reduce_scatter, alltoall, broadcast, send/recv over
ProcessGroupNCCL, ``paddle/fluid/distributed/collective/process_group_nccl.cc``).

TPU-native: these are thin wrappers over lax collectives, valid INSIDE
``shard_map``/``pmap`` where a mesh axis name is bound. Outside shard_map,
GSPMD inserts collectives automatically from shardings — prefer that; use
these only where the schedule must be explicit (pipeline, ring attention,
MoE all-to-all).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size
from paddle_tpu.observability import METRICS
from paddle_tpu.utils.faults import fault_point

# Host-side collective accounting. These wrappers run at TRACE time (the
# executed program is XLA's), so the counters measure how many collective
# ops each compiled program CONTAINS — per-trace, not per-device-launch.
# That is the number that matters for schedule review ("why does this
# step all-gather 40 times?") and it is exactly once per compilation, so
# the hot path stays untouched.
_COLL_OPS = METRICS.counter(
    "collective_ops_total", "collective ops traced, by op kind",
    labelnames=("op",))
_COLL_BYTES = METRICS.counter(
    "collective_bytes_total",
    "per-member payload bytes of traced collective ops", labelnames=("op",))


def _count(op: str, x):
    _COLL_OPS.inc(op=op)
    try:
        _COLL_BYTES.inc(x.size * x.dtype.itemsize, op=op)
    except (AttributeError, TypeError):   # python scalars / exotic leaves
        pass


# ReduceOp parity (ref communication/reduce.py)
class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def all_reduce(x, op: str = ReduceOp.SUM, *, axis_name: str):
    # chaos site (ROADMAP multi-host slice): an installed rule can raise
    # (collective timeout → surfaces as a trace-time error the elastic
    # layer restarts through) or stall (straggler host). Host-side at
    # trace time — nothing is injected into the compiled program.
    fault_point("collective.all_reduce", op=op, axis_name=axis_name)
    _count("all_reduce", x)
    if op == ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis_name)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x), axis_name))
    raise ValueError(op)


def all_gather(x, *, axis_name: str, axis: int = 0, tiled: bool = True):
    _count("all_gather", x)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, *, axis_name: str, axis: int = 0):
    _count("reduce_scatter", x)
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, *, axis_name: str, split_axis: int, concat_axis: int):
    _count("all_to_all", x)
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def broadcast(x, src: int = 0, *, axis_name: str):
    """Every member gets member `src`'s value."""
    _count("broadcast", x)
    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    sel = jnp.where(jnp.arange(n) == src, 1.0, 0.0).astype(x.dtype)
    gathered = lax.all_gather(x, axis_name, axis=0)
    return jnp.tensordot(sel, gathered, axes=([0], [0])).astype(x.dtype)


def permute(x, perm: list[tuple[int, int]], *, axis_name: str):
    """Point-to-point send/recv pattern (ref send/recv): perm = [(src,dst)...]."""
    _count("permute", x)
    return lax.ppermute(x, axis_name, perm)


def shift(x, offset: int = 1, *, axis_name: str):
    """Ring shift: member i's value goes to member (i+offset) % n."""
    _count("shift", x)
    n = axis_size(axis_name)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def barrier(*, axis_name: str):
    """Collectives are compiler-ordered on TPU; a psum serves as sync point."""
    return lax.psum(jnp.zeros((), jnp.float32), axis_name)


def reduce(x, dst: int = 0, op: str = ReduceOp.SUM, *, axis_name: str):
    """Reduce to member ``dst`` (ref communication/reduce.py). Other members
    get their input back unchanged — on TPU the all-reduce already rode ICI;
    masking to dst would only add work, so this is all_reduce + select."""
    red = all_reduce(x, op, axis_name=axis_name)
    return jnp.where(lax.axis_index(axis_name) == dst, red, x)


def scatter(x, src: int = 0, *, axis_name: str):
    """Member ``src``'s value, split over the axis: member i receives the
    i-th chunk of src's leading dim (ref communication/scatter.py)."""
    full = broadcast(x, src, axis_name=axis_name)
    n = axis_size(axis_name)
    i = lax.axis_index(axis_name)
    if full.shape[0] % n != 0:
        raise ValueError(
            f"scatter: leading dim {full.shape[0]} must divide evenly over "
            f"{n} members (reference scatter requires an exact split)")
    chunk = full.shape[0] // n
    return lax.dynamic_slice_in_dim(full, i * chunk, chunk, axis=0)


def gather(x, dst: int = 0, *, axis_name: str, axis: int = 0):
    """All members' values concatenated; valid on every member (TPU
    collectives are SPMD — restricting to dst would not save ICI traffic)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _p2p_edge(x, src: int, dst: int, axis_name: str):
    out = lax.ppermute(x, axis_name, [(src, dst)])
    return jnp.where(lax.axis_index(axis_name) == dst, out, x)


def send(x, dst: int, *, src: int, axis_name: str):
    """P2P send (ref communication/send.py). SPMD note: the reference calls
    send on one rank and recv on another; under XLA every member traces the
    same program, so both endpoints must be static — ``send``/``recv`` are
    two names for the same single-edge ppermute. Member ``dst`` receives
    ``src``'s value; everyone else keeps their input."""
    return _p2p_edge(x, src, dst, axis_name)


def recv(x, src: int, *, dst: int, axis_name: str):
    """P2P receive — see ``send``."""
    return _p2p_edge(x, src, dst, axis_name)


def all_gather_object(obj, group=None):
    """Gather arbitrary picklable objects across hosts (ref
    communication/all_gather.py:all_gather_object). Host-side (not traced):
    single-process returns [obj]; multi-host pickles into padded uint8
    arrays and rides ``multihost_utils.process_allgather``."""
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils
    data = np.frombuffer(pickle.dumps(obj), np.uint8)
    n = np.asarray([data.size], np.int64)
    sizes = multihost_utils.process_allgather(n)
    cap = int(sizes.max())
    padded = np.zeros(cap, np.uint8)
    padded[:data.size] = data
    gathered = multihost_utils.process_allgather(padded)
    return [pickle.loads(gathered[i, :int(sizes[i])].tobytes())
            for i in range(gathered.shape[0])]
