"""paddle.distributed namespace."""
from . import (  # noqa: F401
    auto_parallel,
    collective,
    compress,
    passes,
    checkpoint,
    fleet_executor,
    elastic,
    env,
    fleet,
    launch,
    mesh,
    rpc,
    sharding,
    stream,
    topology,
    utils,
)
from .auto_parallel import ProcessMesh, shard_op, shard_tensor  # noqa: F401
from .collective import (  # noqa: F401
    Group,
    P2POp,
    ReduceOp,
    all_gather,
    all_gather_object,
    all_reduce,
    alltoall,
    alltoall_single,
    barrier,
    batch_isend_irecv,
    broadcast,
    broadcast_object_list,
    destroy_process_group,
    get_backend,
    get_group,
    gloo_barrier,
    gloo_init_parallel_env,
    gloo_release,
    irecv,
    is_available,
    isend,
    new_group,
    partial_allgather,
    partial_recv,
    partial_send,
    recv,
    reduce,
    reduce_scatter,
    scatter,
    scatter_object_list,
    send,
    wait,
)
from . import io  # noqa: F401
from .entry import (  # noqa: F401
    CountFilterEntry,
    ProbabilityEntry,
    ShowClickEntry,
)
from ..framework.dataset import (  # noqa: F401
    InMemoryDataset,
    QueueDataset,
)
from ..parallel.mp_layers import split  # noqa: F401
from .env import (  # noqa: F401
    ParallelEnv,
    get_rank,
    get_world_size,
    init_parallel_env,
    is_initialized,
)
from .topology import CommunicateTopology, HybridCommunicateGroup  # noqa: F401


def spawn(func, args=(), nprocs=-1, join=True, **kwargs):
    """reference paddle.distributed.spawn (distributed/spawn.py): fork
    nprocs worker processes on this node, each with rank env set, and run
    `func(*args)` in each. On a TPU the single-controller SPMD model owns
    all local chips from one process, so nprocs defaults to 1.

    ``nprocs > 1`` on a TPU backend is REFUSED: a chip belongs to one
    process at a time and nothing here pins each child to its own chip,
    so the children would fail or hang at backend start-up. Multi-process
    spawn is the CPU-simulation/test path; the children inherit the
    parent's platform, which this call has just checked is not a TPU."""
    import multiprocessing as mp

    if nprocs in (-1, None):
        nprocs = 1
    if nprocs < 1:
        raise ValueError("spawn: nprocs must be >= 1, got %r" % nprocs)
    if nprocs == 1:
        func(*args)
        return None
    refuse_multiprocess_on_tpu("distributed.spawn(nprocs=%d)" % nprocs)
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_worker,
                        args=(func, args, rank, nprocs))
        p.start()
        procs.append(p)
    if not join:
        return procs
    for p in procs:
        p.join()
    bad = [(i, p.exitcode) for i, p in enumerate(procs) if p.exitcode != 0]
    if bad:
        raise RuntimeError("spawn: worker(s) failed: %s" % bad)
    return None


def refuse_multiprocess_on_tpu(what):
    """One process per chip. A launcher about to start several processes
    that each initialise a JAX backend calls this first: on a TPU
    backend it raises, because nothing assigns each child its own chip
    yet (running N one-chip replicas from ONE process is ROADMAP D5/R2).
    Where ``JAX_PLATFORMS`` already rules a TPU out nothing is
    initialised (a launcher parent stays off JAX); otherwise this
    process's backend is, to find out — which on a TPU is exactly why
    the children could not have had the chip."""
    import os

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "%s: refused on a TPU backend — a chip belongs to one "
            "process at a time and nothing pins each child to its own "
            "chip. Drive every local chip from one process, or run the "
            "CPU simulation under JAX_PLATFORMS=cpu." % what)


def _spawn_worker(func, args, rank, nprocs):
    # spawn children inherit the parent environment; only rank vars differ
    import os

    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    func(*args)


def ParallelMode():
    class _M:
        DATA_PARALLEL = 0
        TENSOR_PARALLEL = 1
        PIPELINE_PARALLEL = 2
        SHARDING_PARALLEL = 3

    return _M
