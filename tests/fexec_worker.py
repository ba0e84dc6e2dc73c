"""Cross-rank FleetExecutor worker: a 2-stage pipeline split over two OS
processes, interceptor messages riding the MessageBus (TCP-store queues)
— the reference brpc-bus deployment shape (fleet_executor.cc +
message_bus.cc). Rank 0: Source(0) + stage A(1); rank 1: stage B(2) +
Sink(3)."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from paddle_tpu.distributed.fleet_executor import (  # noqa: E402
    FleetExecutor,
    MessageBus,
    TaskNode,
)
from paddle_tpu.distributed.store import TCPStore  # noqa: E402


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    port = int(os.environ["PADDLE_MASTER"].rpartition(":")[2])
    n_micro = int(os.environ.get("FEXEC_MICRO", "5"))
    store = TCPStore(port=port, is_master=(rank == 0))
    bus = MessageBus(store, rank)
    if rank == 0:
        src = TaskNode(node_type="Source", task_id=0,
                       max_run_times=n_micro, payload=lambda i: i * 10)
        a = TaskNode(node_type="Compute", task_id=1,
                     max_run_times=n_micro, payload=lambda x: x + 1)
        src.add_downstream_task(1)
        a.add_upstream_task(0)
        a.add_downstream_task(2)  # hosted on rank 1
        ex = FleetExecutor([src, a], bus=bus)
        ex.run(timeout=60)
        print("RANK0_DONE")
    else:
        b = TaskNode(node_type="Compute", task_id=2,
                     max_run_times=n_micro, payload=lambda x: x * 2)
        sink = TaskNode(node_type="Sink", task_id=3,
                        max_run_times=n_micro)
        b.add_upstream_task(1)  # hosted on rank 0
        b.add_downstream_task(3)
        sink.add_upstream_task(2)
        ex = FleetExecutor([b, sink], bus=bus)
        results = ex.run(timeout=60)
        print("RESULTS", results)
    store.close()


if __name__ == "__main__":
    main()
