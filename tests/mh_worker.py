"""Worker process for the multi-host SPMD tests: joins a 2-process x
4-device CPU mesh (jax.distributed + Gloo collectives), executes the
planner-emitted SpmdAggregateExec, and reports results + which scan
partitions THIS process read, as one JSON line on stdout."""

import json
import os
import sys


def main() -> None:
    pid = int(sys.argv[1])
    n_proc = int(sys.argv[2])
    port = sys.argv[3]
    data_dir = sys.argv[4]
    query = sys.argv[5]  # "int_keys" | "string_keys"

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=n_proc, process_id=pid
    )

    import pyarrow as pa

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.logical import col, functions as F
    from ballista_tpu.ops.stage import FusedAggregateStage
    from ballista_tpu.parallel.spmd_stage import SpmdAggregateExec
    from ballista_tpu.physical.plan import TaskContext

    read_partitions = []
    orig = FusedAggregateStage._scan_batches

    def tracking(self, partition, ctx):
        read_partitions.append(partition)
        return orig(self, partition, ctx)

    FusedAggregateStage._scan_batches = tracking

    cfg = BallistaConfig(
        {
            "ballista.executor.backend": "tpu",
            "ballista.tpu.spmd_stages": "true",
            "ballista.tpu.mesh": "data:8",
        }
    )
    ctx = ExecutionContext(cfg)
    ctx.register_parquet("t", data_dir)
    # int_keys: low-cardinality (unrolled program); highcard: the sorted
    # chunked-segment program (hk has thousands of groups); string_keys:
    # collective decline to host
    key = {"int_keys": "k", "highcard": "hk", "string_keys": "s"}[query]
    df = ctx.table("t").aggregate(
        [col(key)],
        [F.sum(col("v")).alias("sv"), F.count(col("v")).alias("c"),
         F.min(col("v")).alias("mn"), F.sum(col("w")).alias("sw")],
    )
    phys = ctx.create_physical_plan(df.logical_plan())
    stages = DistributedPlanner(cfg).plan_query_stages("mh", phys)

    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next(s for s in (find(st) for st in stages) if s is not None)
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="mh")
    out = pa.Table.from_batches(list(spmd.execute(0, tctx))).sort_by(key)
    print(
        json.dumps(
            {
                "pid": pid,
                "path": spmd.last_path,
                "read_partitions": sorted(set(read_partitions)),
                "result": {
                    k: [
                        round(v, 6) if isinstance(v, float) else v
                        for v in out.column(k).to_pylist()
                    ]
                    for k in out.schema.names
                },
            }
        )
    )


if __name__ == "__main__":
    main()
