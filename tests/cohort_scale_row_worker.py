"""Worker for the cohort store's memory-model tests (tests/test_cohort.py).

One row a process, so each row's ``ru_maxrss`` high-water mark is its own:
run cohort rounds over a simulated population and print, as the last line
of standard output, the peak host RSS beside the store's apparent and
resident bytes (docs/scaling.md "Memory model").

Usage: cohort_scale_row_worker.py TOTAL_CLIENTS STORE ROUNDS [STORE_PATH]
"""

import json
import os
import resource
import sys

COHORT_SIZE = 64


def main():
    total, store, rounds = int(sys.argv[1]), sys.argv[2], int(sys.argv[3])
    store_path = sys.argv[4] if len(sys.argv) > 4 else None
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from fedtpu.cohort.scheduler import run_cohort_experiment
    from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                               ModelConfig, OptimConfig, RunConfig,
                               ShardConfig)
    from fedtpu.telemetry.metrics import default_registry

    cfg = ExperimentConfig(
        # The rows measure state scale, not data scale: the sample pool
        # stays fixed while clients grow.
        data=DataConfig(csv_path=None, synthetic_rows=4096),
        shard=ShardConfig(num_clients=total),
        model=ModelConfig(input_dim=14, num_classes=2, hidden_sizes=(8,)),
        optim=OptimConfig(),
        fed=FedConfig(rounds=rounds, cohort_size=COHORT_SIZE,
                      client_store=store, client_store_path=store_path),
        run=RunConfig(log_every=max(1, rounds), rounds_per_step=1),
    )
    res = run_cohort_experiment(cfg, verbose=False)
    reg = default_registry()
    print(json.dumps({  # fedtpu: noqa[FTP005] stdout IS the worker->parent IPC protocol
        "rounds": res.rounds_run,
        # Linux reports ru_maxrss in KiB.
        "peak_rss_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
        "store_apparent_bytes": int(
            reg.gauge("client_store_apparent_bytes").value),
        "store_resident_bytes": int(
            reg.gauge("client_store_resident_bytes").value),
    }), flush=True)


if __name__ == "__main__":
    main()
