"""Collective communication API.

Parity: reference python/paddle/distributed/communication/ (all_reduce,
all_gather, reduce_scatter, alltoall, broadcast, send/recv, Group) and the
C++ ProcessGroup (distributed/collective/process_group.h:53).

TPU-native design ("ProcessGroupICI", SURVEY §5): a Group is a mesh axis.
Each collective has two execution modes:

1. **Traced** (inside shard_map/pjit): the functions detect tracers and emit
   the XLA collective (lax.psum / all_gather / ppermute / all_to_all) on the
   group's axis name — collectives fuse into the surrounding step program and
   overlap with compute via XLA latency-hiding scheduling (the role of the
   reference's separate comm streams + WaitCompute/WaitComm events).

2. **Eager**: a cached one-op compiled module (jit of shard_map) applied to a
   global array sharded over the group axis; dim `shard_axis` (default 0) of
   the tensor is the per-rank dimension. This mirrors eager ProcessGroup
   semantics where each rank holds one shard.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map  # noqa: F401  (re-exported: one import site)

from ..core.tensor import Tensor
from ..monitor import flight_recorder as _flight
from . import mesh as _mesh


_REDUCE_OPS = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}


def _rec_api(op, g, v, reduce_op=None, strict_shape=False):
    """Flight-record an API-level eager collective with its axis/group
    identity (the pg layer records transport ops; the depth guard keeps
    only this outermost record). The group tag is the pg PREFIX — the
    same identity the timeout diagnoser scopes its stream comparison
    by, and unique per group even over one rank set."""
    pg = getattr(g, "pg", None)
    return _flight.get_flight_recorder().record(
        op, reduce_op=reduce_op,
        shape=tuple(getattr(v, "shape", ()) or ()),
        dtype=str(getattr(v, "dtype", None)),
        axis=getattr(g, "axis", None),
        group=(pg.prefix if pg is not None
               else getattr(g, "id", None)),
        strict_shape=strict_shape)


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group.

    Two coexisting identities (SURVEY §5 "ProcessGroupICI"):
    - a mesh axis (`axis`) for SPMD/traced collectives inside compiled
      steps — XLA inserts the ICI collective;
    - optionally a process-level StoreProcessGroup (`pg`) when
      init_parallel_env brought up a multi-process world — eager
      collectives then have true per-rank semantics
      (reference process_group.h:53 ProcessGroup).
    """

    def __init__(self, axis="dp", mesh=None, ranks=None, id=0, pg=None):
        self.axis = axis
        self._mesh = mesh
        self.id = id
        self.ranks = ranks
        self.pg = pg

    @property
    def mesh(self):
        return self._mesh or _mesh.get_mesh()

    @property
    def nranks(self):
        if self.pg is not None:
            return self.pg.world_size
        if self.ranks and _world_pg() is not None:
            return len(self.ranks)
        return _mesh.axis_size(self.axis, self.mesh)

    world_size = nranks

    @property
    def rank(self):
        """Process rank within the group; -1 if this process is not a
        member (reference Group semantics). SPMD single-process is rank 0."""
        if self.pg is not None:
            return self.pg.rank
        if self.ranks:
            from .process_group import world_rank

            return (self.ranks.index(world_rank())
                    if world_rank() in self.ranks else -1)
        return 0

    def is_member(self):
        return self.rank >= 0

    def get_group_rank(self, rank):
        if self.ranks:
            return self.ranks.index(rank) if rank in self.ranks else -1
        return rank

    def __repr__(self):
        return "Group(axis=%s, nranks=%d)" % (self.axis, self.nranks)


_default_group = None
_groups = {}


def _world_pg():
    from .process_group import get_world_group

    return get_world_group()


def _get_default_group():
    global _default_group
    pg = _world_pg()
    if _default_group is None or _default_group.pg is not pg:
        mesh = _mesh.get_mesh()
        _default_group = Group(axis=mesh.axis_names[0], mesh=mesh, pg=pg)
    return _default_group


def new_group(ranks=None, backend=None, axis=None, timeout=None):
    """reference communication/group.py new_group. TPU mapping: groups are
    mesh axes; `axis` selects one. With a multi-process world (store
    backend), ranks-based groups become true subgroups; single-process
    SPMD maps them onto the default axis (the partitioner needs axes,
    not rank lists)."""
    pg = _world_pg()
    sub = None
    gid = len(_groups) + 1
    if pg is not None and ranks:
        ranks = sorted(ranks)
        if pg.rank in ranks:
            from .process_group import StoreProcessGroup

            # gid in the prefix: two groups over the same rank set must
            # not share a store key namespace (every member computes the
            # same gid — groups are created collectively, in order)
            sub = StoreProcessGroup(
                pg.store, ranks.index(pg.rank), len(ranks),
                prefix="pg/g%d/%s" % (gid, "_".join(map(str, ranks))))
    g = Group(axis=axis or _mesh.get_mesh().axis_names[0], ranks=ranks,
              id=gid, pg=sub)
    _groups[g.id] = g
    return g


def get_group(gid=0):
    return _groups.get(gid, _get_default_group())


def _is_tracer(v):
    return isinstance(v, jax.core.Tracer)


def _axis_in_scope(axis):
    """True if `axis` is a bound axis name (we're inside shard_map/pmap)."""
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap_like(x, v):
    return Tensor(v) if isinstance(x, Tensor) else v


@functools.lru_cache(maxsize=256)
def _compiled_collective(kind, axis, shape, dtype, extra=()):
    """Cached one-op XLA module over the mesh (the ProcessGroupICI analog of
    the reference's cached NCCL launch per ring)."""
    mesh = _mesh.get_mesh()
    spec = P(axis)

    if kind == "all_reduce_sum":
        f = lambda v: jax.lax.psum(v, axis)
        in_spec, out_spec = spec, P()
    elif kind == "all_reduce_max":
        f = lambda v: jax.lax.pmax(v, axis)
        in_spec, out_spec = spec, P()
    elif kind == "all_reduce_min":
        f = lambda v: jax.lax.pmin(v, axis)
        in_spec, out_spec = spec, P()
    elif kind == "all_gather":
        f = lambda v: jax.lax.all_gather(v, axis, tiled=True)
        in_spec, out_spec = spec, P()
    elif kind == "reduce_scatter":
        f = lambda v: jax.lax.psum_scatter(v, axis, tiled=True)
        in_spec, out_spec = spec, spec
    elif kind == "all_to_all":
        f = lambda v: jax.lax.all_to_all(v, axis, split_axis=1,
                                         concat_axis=0, tiled=True)
        in_spec, out_spec = spec, spec
    else:
        raise ValueError(kind)
    fn = shard_map(f, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
                   check_vma=False)
    return jax.jit(fn)


def _eager_shard(x, axis):
    mesh = _mesh.get_mesh()
    return jax.device_put(x, NamedSharding(mesh, P(axis)))


def _pg_of(g):
    """Process backend for eager mode, or None for single-process SPMD."""
    pg = g.pg
    if pg is not None and pg.world_size > 1:
        return pg
    return None


def _np(v):
    import numpy as _numpy

    return _numpy.asarray(v)


def _store_result(tensor, out):
    out = jnp.asarray(out)
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    g = group or _get_default_group()
    v = _unwrap(tensor)
    if _is_tracer(v):
        if op == ReduceOp.SUM:
            out = jax.lax.psum(v, g.axis)
        elif op == ReduceOp.MAX:
            out = jax.lax.pmax(v, g.axis)
        elif op == ReduceOp.MIN:
            out = jax.lax.pmin(v, g.axis)
        elif op == ReduceOp.AVG:
            out = jax.lax.pmean(v, g.axis)
        else:
            raise ValueError(op)
        return _wrap_like(tensor, out)
    pg = _pg_of(g)
    if pg is not None:
        with _rec_api("all_reduce", g, v, reduce_op=op,
                      strict_shape=True):
            return _store_result(tensor, pg.allreduce(_np(v), op))
    if g.nranks == 1:
        return tensor
    kind = {"sum": "all_reduce_sum", "max": "all_reduce_max",
            "min": "all_reduce_min"}[op if op != ReduceOp.AVG else "sum"]
    fn = _compiled_collective(kind, g.axis, tuple(v.shape), str(v.dtype))
    out = fn(_eager_shard(v, g.axis))
    if op == ReduceOp.AVG:
        out = out / g.nranks
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return out


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    g = group or _get_default_group()
    v = _unwrap(tensor)
    if _is_tracer(v):
        out = jax.lax.all_gather(v, g.axis)
        # traced mode returns stacked [nranks, ...]
        return _wrap_like(tensor, out)
    pg = _pg_of(g)
    if pg is not None:
        # strict: tensor all_gather requires shape/dtype agreement —
        # validate BEFORE the wire exchange and name the mismatched
        # rank (object collectives go through pg.allgather directly
        # with legitimately rank-varying payloads)
        with _rec_api("all_gather", g, v):
            parts = pg.allgather(_np(v), strict=True)
        if tensor_list is not None:
            tensor_list.extend(Tensor(jnp.asarray(p)) for p in parts)
            return tensor_list
        return Tensor(jnp.concatenate([jnp.asarray(p) for p in parts],
                                      axis=0))
    if g.nranks == 1:
        if tensor_list is not None:
            tensor_list.append(
                tensor if isinstance(tensor, Tensor) else Tensor(v))
            return tensor_list
        return tensor
    fn = _compiled_collective("all_gather", g.axis, tuple(v.shape),
                              str(v.dtype))
    out = fn(_eager_shard(v, g.axis))
    if tensor_list is not None:
        parts = jnp.split(out, g.nranks, axis=0)
        tensor_list.extend(Tensor(p) for p in parts)
        return tensor_list
    return Tensor(out)


def reduce_scatter(tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group=None, sync_op=True):
    g = group or _get_default_group()
    src = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    if isinstance(src, (list, tuple)):
        v = jnp.concatenate([_unwrap(t) for t in src], axis=0)
    else:
        v = _unwrap(src)
    if _is_tracer(v):
        return _wrap_like(tensor, jax.lax.psum_scatter(v, g.axis, tiled=True))
    pg = _pg_of(g)
    if pg is not None:
        # true per-rank semantics: this rank gets its reduced [d0/n] shard
        with _rec_api("reduce_scatter", g, v, reduce_op=op,
                      strict_shape=True):
            return _store_result(tensor, pg.reduce_scatter(_np(v), op))
    if g.nranks == 1:
        if isinstance(tensor, Tensor):
            tensor._value = v
            return tensor
        return Tensor(v)
    fn = _compiled_collective("reduce_scatter", g.axis, tuple(v.shape),
                              str(v.dtype))
    out = fn(_eager_shard(v, g.axis))
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def alltoall(in_tensor_or_list, out_tensor_or_list=None, group=None,
             sync_op=True):
    g = group or _get_default_group()
    if isinstance(in_tensor_or_list, (list, tuple)):
        v = jnp.concatenate([_unwrap(t) for t in in_tensor_or_list], axis=0)
        as_list = True
    else:
        v = _unwrap(in_tensor_or_list)
        as_list = False
    if _is_tracer(v):
        n = g.nranks
        r = v.reshape((n, v.shape[0] // n) + v.shape[1:])
        out = jax.lax.all_to_all(r, g.axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        out = out.reshape(v.shape)
        return _wrap_like(in_tensor_or_list, out)
    pg = _pg_of(g)
    if pg is not None:
        # per-rank semantics (reference alltoall: dim0 % nranks == 0)
        with _rec_api("all_to_all", g, v, strict_shape=True):
            out = jnp.asarray(pg.alltoall(_np(v)))
    elif g.nranks == 1:
        out = v
    else:
        # Single-process global view of the exchange: rank r's chunk j
        # becomes rank j's chunk r — a (src, dst) transpose of dim 0
        # (hence the nranks^2 divisibility of the GLOBAL dim; each
        # per-rank shard only needs nranks). device_put re-shards the
        # permuted array, which is the actual ICI all-to-all.
        n = g.nranks
        if v.shape[0] % (n * n):
            raise ValueError(
                "alltoall (single-process global view) requires dim0 (%d) "
                "divisible by nranks^2 (%d); per-rank shards need only "
                "dim0 %% nranks" % (v.shape[0], n * n))
        r = v.reshape((n, n, v.shape[0] // (n * n)) + v.shape[1:])
        out = jnp.swapaxes(r, 0, 1).reshape(v.shape)
        out = _eager_shard(out, g.axis)
    if as_list and out_tensor_or_list is not None:
        parts = jnp.split(out, g.nranks, axis=0)
        out_tensor_or_list.extend(Tensor(p) for p in parts)
        return out_tensor_or_list
    return Tensor(out)


def broadcast(tensor, src=0, group=None, sync_op=True):
    g = group or _get_default_group()
    v = _unwrap(tensor)
    if _is_tracer(v):
        # broadcast within an SPMD program: select src's shard and replicate
        idx = jax.lax.axis_index(g.axis)
        out = jax.lax.psum(jnp.where(idx == src, v, jnp.zeros_like(v)), g.axis)
        return _wrap_like(tensor, out)
    pg = _pg_of(g)
    if pg is not None:
        # rank-aware: every rank receives src's tensor
        with _rec_api("broadcast", g, v):
            return _store_result(tensor, pg.broadcast(_np(v), src))
    # SPMD single process: arrays are already globally addressed; replicating
    # is a device_put with a replicated sharding.
    if isinstance(tensor, Tensor):
        tensor._value = _mesh.replicate(v)
        return tensor
    return _mesh.replicate(v)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is not None:
        # true rooted-reduce semantics: only dst's tensor changes
        out = pg.reduce(_np(_unwrap(tensor)), dst, op)
        if pg.rank == dst:
            return _store_result(tensor, out)
        return tensor
    # single-process SPMD: an all-reduce + owner view is the natural
    # lowering; the rooted form saves no ICI time on TPU tori.
    return all_reduce(tensor, op=op, group=g)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is not None:
        chunks = None
        if pg.rank == src:
            import numpy as _numpy

            # src supplies a tensor list, or one tensor split n ways
            chunks = ([_np(_unwrap(t)) for t in tensor_list]
                      if tensor_list is not None else
                      list(_numpy.split(_np(_unwrap(tensor)),
                                        pg.world_size, axis=0)))
        return _store_result(tensor, pg.scatter(chunks, src))
    if tensor_list is not None:
        # single-process SPMD: this process's rank within the group
        # selects the chunk (rank 0 unless ranks-groups say otherwise)
        full = jnp.concatenate([_unwrap(t) for t in tensor_list], axis=0)
        n = g.nranks
        part = jnp.split(full, n, axis=0)[max(g.rank, 0)]
        if isinstance(tensor, Tensor):
            tensor._value = part
            return tensor
        return Tensor(part)
    return tensor


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P send (reference send_v2). Eager p2p needs a process world:
    inside compiled steps use ppermute (pipeline runtime); between
    processes it rides the store backend."""
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is not None:
        pg.send(_np(_unwrap(tensor)), dst)
        return
    raise RuntimeError(
        "eager send/recv within one process has no SPMD analog: use "
        "paddle_tpu.parallel p2p helpers (ppermute) inside a compiled "
        "step, as the pipeline runtime does; between processes call "
        "init_parallel_env first (PADDLE_TRAINERS_NUM > 1)")


def recv(tensor, src=0, group=None, sync_op=True):
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is not None:
        out = pg.recv(src)
        return _store_result(tensor, out)
    raise RuntimeError(
        "eager send/recv within one process has no SPMD analog: use "
        "paddle_tpu.parallel p2p helpers (ppermute) inside a compiled "
        "step; between processes call init_parallel_env first "
        "(PADDLE_TRAINERS_NUM > 1)")


class Task:
    """Completion handle returned by async-flavored collectives (reference
    ProcessGroup::Task, distributed/collective/process_group.h:53). The
    store backend completes operations synchronously, so the handle is a
    finished-state record with the result attached; `wait()` exists for
    API compatibility with code written against NCCL's async tasks."""

    def __init__(self, result=None):
        self._result = result

    def wait(self, timeout=None):
        return True

    def is_completed(self):
        return True

    def result(self):
        return self._result


def isend(tensor, dst=0, group=None):
    """Async-flavored send (reference communication/send.py isend).
    The store backend's send is a non-blocking put, so the task is
    complete on return."""
    send(tensor, dst=dst, group=group, sync_op=False)
    return Task()


def irecv(tensor, src=0, group=None):
    """Async-flavored recv (reference communication/recv.py irecv): blocks
    until the matching send's payload lands, writes it into `tensor`, and
    returns a completed Task."""
    out = recv(tensor, src=src, group=group, sync_op=False)
    return Task(out)


class P2POp:
    """One point-to-point operation for batch_isend_irecv (reference
    communication/batch_isend_irecv.py:26 P2POp): op is `isend` or
    `irecv`, tensor the buffer, peer the remote rank."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv):
            raise RuntimeError(
                "The op for p2p_op_list must be paddle.distributed.isend "
                "or paddle.distributed.irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of p2p ops (reference batch_isend_irecv.py:84).

    All sends are issued before any recv: the reference brackets the batch
    in a NCCL group so member ops can't deadlock on issue order; with the
    store backend, sends are non-blocking puts, so issuing them first
    gives the same guarantee for any self-consistent batch (e.g. the ring
    exchange where every rank both sends and recvs)."""
    if not p2p_op_list:
        raise RuntimeError("p2p_op_list must not be empty")
    if not all(isinstance(p, P2POp) for p in p2p_op_list):
        raise RuntimeError("p2p_op_list must contain only P2POp instances")
    tasks = [None] * len(p2p_op_list)
    order = ([i for i, p in enumerate(p2p_op_list) if p.op is isend]
             + [i for i, p in enumerate(p2p_op_list) if p.op is irecv])
    for i in order:
        p = p2p_op_list[i]
        tasks[i] = p.op(p.tensor, p.peer, group=p.group)
    return tasks


def _flat_chunk_bounds(numel, nranks, rank_id):
    if numel % nranks:
        raise ValueError(
            "partial collective: tensor numel (%d) must be divisible by "
            "nranks (%d)" % (numel, nranks))
    chunk = numel // nranks
    return chunk * rank_id, chunk * (rank_id + 1)


def partial_send(tensor, dst=0, nranks=1, rank_id=0, group=None):
    """Send flat elements [rank_id*numel/nranks, (rank_id+1)*numel/nranks)
    of `tensor` (reference partial_send_op: the PP p2p slice primitive)."""
    v = _np(_unwrap(tensor))
    lo, hi = _flat_chunk_bounds(v.size, nranks, rank_id)
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is None:
        raise RuntimeError(
            "partial_send needs a multi-process world (init_parallel_env)")
    pg.send(v.reshape(-1)[lo:hi], dst)


def partial_recv(tensor, src=0, nranks=1, rank_id=0, group=None):
    """Receive into the flat [rank_id] chunk of `tensor`, leaving the other
    chunks untouched (reference partial_recv_op)."""
    v = _np(_unwrap(tensor)).copy()
    lo, hi = _flat_chunk_bounds(v.size, nranks, rank_id)
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is None:
        raise RuntimeError(
            "partial_recv needs a multi-process world (init_parallel_env)")
    flat = v.reshape(-1)
    flat[lo:hi] = pg.recv(src).reshape(-1)
    return _store_result(tensor, flat.reshape(v.shape))


def partial_allgather(tensor, nranks=1, rank_id=0, group=None):
    """Each rank contributes its flat [rank_id] chunk; every rank gets the
    full tensor with chunk r filled by rank r (reference
    partial_allgather_op, used to reassemble partial_send/recv'd
    activations). In-place on `tensor`."""
    v = _np(_unwrap(tensor))
    lo, hi = _flat_chunk_bounds(v.size, nranks, rank_id)
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is None:
        raise RuntimeError(
            "partial_allgather needs a multi-process world "
            "(init_parallel_env)")
    if nranks != pg.world_size:
        # world_size chunks of numel/nranks elements only reassemble into
        # tensor.shape when the two agree (reference partial_allgather_op
        # asserts nranks == ring size the same way)
        raise ValueError(
            "partial_allgather: nranks (%d) must equal the group world "
            "size (%d)" % (nranks, pg.world_size))
    # never compressed: these are pipeline-stage ACTIVATIONS — forward
    # math must stay exact regardless of the grad-sync flag (the int8
    # wire format is a gradient-communication trade, not a model change)
    parts = pg.allgather(v.reshape(-1)[lo:hi], compressed=False)
    import numpy as _numpy

    flat = _numpy.concatenate([_numpy.asarray(p).reshape(-1) for p in parts])
    return _store_result(tensor, flat.reshape(v.shape))


def barrier(group=None):
    g = group or _get_default_group()
    pg = _pg_of(g)
    if pg is not None:
        pg.barrier()
    # All outstanding XLA work on all local devices must finish.
    jax.block_until_ready(
        jax.device_put(jnp.zeros(()), jax.devices()[0]))


def get_rank(group=None):
    from . import env

    return env.get_rank(group)


def get_world_size(group=None):
    from . import env

    return env.get_world_size(group)


def is_available():
    return True


# traced-mode helpers used by parallel layers --------------------------------

def psum(v, axis):
    return jax.lax.psum(v, axis)


def ppermute(v, axis, perm):
    return jax.lax.ppermute(v, axis, perm)


def axis_index(axis):
    return jax.lax.axis_index(axis)


# -- object collectives + misc compat ----------------------------------------
# (reference python/paddle/distributed/communication/*_object_list: python
# objects pickle onto byte tensors and ride the same transport — here the
# store process group (_world_pg above); a 1-process world is the identity)

def all_gather_object(object_list, obj, group=None):
    """Gather a picklable object from every rank into object_list."""
    import pickle

    pg = _pg_of(group or _get_default_group()) or _world_pg()
    if pg is None or pg.world_size <= 1:
        object_list.extend([obj])
        return
    payload = np.frombuffer(pickle.dumps(obj), np.uint8).copy()
    parts = pg.allgather(payload)
    object_list.extend(pickle.loads(p.tobytes()) for p in parts)


def broadcast_object_list(object_list, src=0, group=None):
    """In-place: every rank ends with src's objects."""
    import pickle

    pg = _pg_of(group or _get_default_group()) or _world_pg()
    if pg is None or pg.world_size <= 1:
        return
    if pg.rank == src:  # only the source serializes; others' payload is
        payload = np.frombuffer(pickle.dumps(list(object_list)),
                                np.uint8).copy()
    else:  # ignored by the store broadcast
        payload = np.empty(0, np.uint8)
    out = pg.broadcast(payload, src)
    object_list[:] = pickle.loads(out.tobytes())


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Rank r receives in_object_list[r] from src."""
    import pickle

    pg = _pg_of(group or _get_default_group()) or _world_pg()
    if pg is None or pg.world_size <= 1:
        # identical semantics to the multi-rank path: this rank gets
        # exactly its own element
        if in_object_list:
            out_object_list.append(in_object_list[0])
        return
    if in_object_list is not None and pg.rank == src and \
            len(in_object_list) != pg.world_size:
        raise ValueError(
            "scatter_object_list: need one object per rank (%d != %d)"
            % (len(in_object_list), pg.world_size))
    if pg.rank == src:
        chunks = [np.frombuffer(pickle.dumps([o]), np.uint8).copy()
                  for o in (in_object_list or [])]
    else:
        chunks = None
    got = pg.scatter(chunks, src)
    out_object_list.extend(pickle.loads(np.asarray(got).tobytes()))


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Single-tensor all-to-all (reference communication/all_to_all.py
    alltoall_single): dim0 splits exchange between ranks. Returns the
    received tensor (out_tensor is also filled when provided)."""
    g = group or _get_default_group()
    n = g.nranks if hasattr(g, "nranks") else get_world_size(g)
    v = _unwrap(in_tensor)
    for sizes in (in_split_sizes, out_split_sizes):
        if sizes is None:
            continue
        if len(set(sizes)) > 1:
            raise NotImplementedError(
                "alltoall_single: only uniform split sizes are "
                "supported (the exchange is a fixed dim0 transpose); "
                "got %s" % (sizes,))
        if sizes and n > 0 and sizes[0] * n != v.shape[0]:
            raise ValueError(
                "alltoall_single: split sizes %s do not cover dim0 %d "
                "across %d ranks" % (sizes, v.shape[0], n))
    # alltoall takes the whole tensor and exchanges uniform dim0 chunks
    received = alltoall(_wrap_like(in_tensor, v), group=group)
    if isinstance(received, (list, tuple)):
        out = jnp.concatenate([_unwrap(t) for t in received], axis=0)
    else:
        out = _unwrap(received)
    if out_tensor is not None and hasattr(out_tensor, "_value"):
        out_tensor._value = out
    return _wrap_like(in_tensor, out)


def wait(tensor, group=None, use_calc_stream=True):
    """reference communication/wait: fence outstanding work on the
    tensor (XLA: block_until_ready)."""
    v = _unwrap(tensor)
    if not _is_tracer(v):
        jax.block_until_ready(v)
    return tensor


def get_backend(group=None):
    """Communication backend name (reference returns NCCL/GLOO; the
    compiled path here is XLA collectives, the eager multi-process
    fallback the TCP store)."""
    pg = _pg_of(group or _get_default_group()) or _world_pg()
    if pg is not None and pg.world_size > 1:
        return "STORE"
    return "XLA"


def destroy_process_group(group=None):
    """Tear down eager process-group state (reference
    communication/group.py destroy_process_group). group=None destroys
    the world; a specific group is removed from the registry."""
    from . import env as _env
    from .process_group import set_world_group

    if group is None:
        set_world_group(None)
        _groups.clear()
        _env._initialized = False
    else:
        _groups.pop(getattr(group, "id", group), None)


# gloo_* compat (reference CPU bootstrap trio): the store process group
# plays gloo's role here

def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    import os

    from . import env as _env

    if _env._initialized:
        import warnings

        warnings.warn(
            "gloo_init_parallel_env: the parallel env is already "
            "initialized; the explicit rank/world arguments cannot take "
            "effect (call it before any init_parallel_env).")
        return
    # the explicit arguments are authoritative (reference semantics) —
    # never let stale launcher env override them
    os.environ["PADDLE_TRAINER_ID"] = str(rank_id)
    os.environ["PADDLE_TRAINERS_NUM"] = str(rank_num)
    os.environ["PADDLE_MASTER"] = server_endpoint
    _env.init_parallel_env()


def gloo_barrier():
    pg = _world_pg()
    if pg is not None:
        pg.barrier("gloo_barrier")


def gloo_release():
    destroy_process_group()
