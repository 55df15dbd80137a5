"""Multi-host (multi-process) support: the DCN story.

The reference scales across nodes by launching more MPI ranks under
``mpirun --hostfile`` — same pickled collectives, now over TCP (SURVEY.md
§2c). fedtpu scales across TPU hosts the JAX way: every host runs THE SAME
single-controller program, ``jax.distributed.initialize`` wires the processes
into one runtime, and ``jax.devices()`` then returns the GLOBAL device list —
so the ('clients',) mesh in fedtpu.parallel.mesh transparently spans hosts.
XLA routes the FedAvg psum over ICI within a host and DCN between hosts; no
fedtpu code changes.

What does change on multi-host is DATA: each process must feed only the
shards of the clients whose devices it holds (addressable devices). Use
``local_client_slice`` to select this host's rows of the packed (C, N, ...)
client batch and ``jax.make_array_from_process_local_data`` to assemble the
global sharded array.

Usage (same script on every host, e.g. a v4-32's 4 workers):

    from fedtpu.parallel import multihost
    multihost.initialize()                      # reads TPU env on each worker
    mesh = make_mesh(num_clients=32)            # 32 global devices
    batch = multihost.distribute_client_batch(packed, mesh)
    ...                                         # identical from here on

Verified single-process (initialize() is a no-op there) AND multi-process:
tests/test_multihost_e2e.py launches two OS processes with four virtual CPU
devices each, wires them into one jax.distributed runtime, and runs the full
round program over the global 8-client mesh — the FedAvg collectives cross
the process boundary over TCP/gloo (the CPU stand-in for DCN) and both
processes hold the identical global model, matching the single-process run.

The COMPLETE orchestration loop is multi-process-aware too (the reference
runs its whole driver under ``mpirun --hostfile``, so fedtpu's
``run_experiment`` must run whole under ``jax.distributed``): host-fetched
metrics are replicated in-graph first (client-sharded leaves are not
addressable across processes), prints/JSONL go to process 0 only, orbax
checkpoints are written as a collective with each process persisting the
client shards it owns, and control flow (early stop, divergence, pipelined
stop) stays consensual because it derives from the replicated metrics.
Executed end-to-end — history, held-out eval, pipelined stop, periodic
checkpoints — across two OS processes by the full-loop tests in
tests/test_multihost_e2e.py, matching the single-process histories exactly.
All three reference drivers are multi-process-validated there: the
multi-round FedAvg loop (both engines: 1-D shard_map and 2-D dp x tp
GSPMD), and the hyperparameter grid search (whose fetched results are
fully replicated, so it runs under jax.distributed unmodified). The
kernel-level worker additionally exercises the explicit ring (ppermute)
aggregation with its hops crossing the process boundary, and true
tp-over-DCN — a transposed ('clients','model') mesh whose model-axis
pairs each span both processes, so the Megatron col/row collectives
themselves ride the inter-process link.

Round 5 widens the executed matrix: the same kernel worker and the full
pipelined-checkpoint loop also run at FOUR processes x two devices each
(every collective crossing three process boundaries); the productized
ASYNC engine runs its full loop across processes too (Bernoulli
arrivals, the FedBuff K-buffer, staleness metrics, collective
checkpoints + resume — matching the single-process trajectory exactly);
and process-death failure propagation is executed, not assumed — see
``initialize``'s docstring for the semantics (the ``comm.Abort``
analogue).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from fedtpu.data.sharding import ClientBatch
from fedtpu.parallel.mesh import client_sharding


_MULTIHOST_ENV_HINTS = (
    "JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
)


def _looks_multihost() -> bool:
    import os
    for var in _MULTIHOST_ENV_HINTS:
        val = os.environ.get(var, "")
        if "," in val or (var.endswith("ADDRESS") and val):
            return True
    for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(var, "1")) > 1:
                return True
        except ValueError:
            continue
    return False


def _enable_cpu_collectives() -> None:
    """Opt in to gloo cross-process collectives when the platform is CPU.

    jax defaults ``jax_cpu_collectives_implementation`` to ``none``, under
    which ANY multi-process computation fails with "Multiprocess
    computations aren't implemented on the CPU backend" — including the
    implicit psum inside ``device_put``'s cross-process equality check.
    gloo-over-TCP is the CPU stand-in for DCN. Must run before the
    backend is created (same contract as ``jax.distributed.initialize``);
    TPU/GPU platforms are untouched."""
    import os
    platforms = (jax.config.jax_platforms
                 or os.environ.get("JAX_PLATFORMS", ""))
    if platforms and platforms.split(",")[0].strip() == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None, **kwargs) -> None:
    """Wire this process into the multi-host runtime.

    Must run before any other JAX call (jax.distributed's contract — even
    ``jax.process_count()`` initializes the backend and poisons it). With no
    arguments, TPU pods auto-discover the topology from the environment.
    Single-process (one host, tests): the failed auto-init is swallowed and
    the program proceeds single-controller. If the environment looks
    multi-host but initialization fails, this RAISES rather than letting
    every worker silently run its own private federation. Extra ``kwargs``
    pass through to ``jax.distributed.initialize`` (e.g.
    ``heartbeat_timeout_seconds``).

    FAILURE PROPAGATION (the reference's ``comm.Abort`` analogue,
    FL_CustomMLP...:203-205, executed in
    tests/test_multihost_e2e.py::test_process_death_terminates_survivors):
    when a process dies mid-run, survivors block in their next
    cross-process collective, the coordination service detects the missed
    heartbeats within ``heartbeat_timeout_seconds`` (jax default 100), and
    every surviving process is TERMINATED with a fatal "distributed
    service detected fatal errors" diagnostic — no hung ranks, no
    survivors silently continuing a partial federation. This is stronger
    than an exception (the runtime cannot guarantee collective state after
    a peer loss); restart + ``--resume`` from the last periodic checkpoint
    is the recovery path, and elastic resume accepts a changed process
    count.
    """
    if coordinator_address is not None or num_processes is not None:
        _enable_cpu_collectives()
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **kwargs)
        return
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:
        if _looks_multihost():
            raise RuntimeError(
                "multi-host environment detected but "
                "jax.distributed.initialize() failed — call "
                "fedtpu.parallel.multihost.initialize() BEFORE any other JAX "
                f"usage (including jax.devices()). Original error: {e}"
            ) from e
        # Not a pod / already-initialized single process — fine.
        return


def initialize_from_env() -> bool:
    """Wire this process into a gang launched by ``fedtpu supervise
    --num-processes N`` (fedtpu.resilience.supervisor.supervise_gang).

    The gang parent sets ``FEDTPU_COORDINATOR`` / ``FEDTPU_NUM_PROCESSES``
    / ``FEDTPU_PROCESS_ID`` per child; this reads them and calls
    ``initialize`` explicitly. Returns True when a gang environment was
    present (and the runtime is now wired), False otherwise — so the CLI
    can call it unconditionally before the first backend touch.

    Peer-death detection note: jax's own coordination-service heartbeat
    (``heartbeat_timeout_seconds``, 100 s by default) is NOT the recovery
    latency here. The gang parent sees the dead child's exit directly and
    tears the rest down with SIGTERM-then-SIGKILL, so survivors blocked in
    a collective are bounded by the supervisor's ``--grace``, not by jax's
    detector.
    """
    import os
    coord = os.environ.get("FEDTPU_COORDINATOR", "")
    if not coord:
        return False
    nprocs = int(os.environ["FEDTPU_NUM_PROCESSES"])
    pid = int(os.environ["FEDTPU_PROCESS_ID"])
    initialize(coordinator_address=coord, num_processes=nprocs,
               process_id=pid)
    return True


def safe_put(x, sharding):
    """``jax.device_put`` minus the implicit cross-process broadcast.

    Putting a HOST value (numpy, or an uncommitted jax array) onto a
    non-fully-addressable sharding makes jax run a psum-backed
    ``multihost_utils.assert_equal`` across every process — one small
    collective PER LEAF (jax dispatch.py, ``_device_put_sharding_impl``).
    At gang startup/resume that is dozens of unfenced gloo/DCN broadcasts
    before the first real round, which is both slow (O(leaves) DCN
    round-trips on a pod) and fragile on restart (observed gloo stream
    misalignment — ``op.preamble.length <= op.nbytes`` aborts — when a
    freshly restarted gang replays them back-to-back).

    Every fedtpu host value is derived from the shared seed, so the
    equality check is vacuous: assemble the global array from the local
    host value instead, which needs no cross-process traffic at all.
    Single-process it IS ``jax.device_put`` (bitwise-identical arrays).

    Contract: ``x`` must be a HOST value — numpy, or a fully-addressable
    jax Array — identical on every process. A non-fully-addressable
    global Array is rejected (its shards cannot be materialized locally;
    reshard it with ``jax.device_put`` instead), and a large committed
    device array pays a device-to-host copy here, so keep device-resident
    data on ``jax.device_put`` too.
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        raise TypeError(
            "safe_put expects a host-local value (numpy, or a "
            "fully-addressable jax.Array) identical on every process; "
            "got a non-fully-addressable global jax.Array — reshard "
            "device-resident global arrays with jax.device_put instead")
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def local_client_slice(num_clients: int, mesh) -> slice:
    """The contiguous rows of the global (C, ...) client axis owned by THIS
    process, given the mesh's device order (clients block-distribute over the
    global device list, C % D == 0)."""
    devices = list(mesh.devices.ravel())
    per_device = num_clients // len(devices)
    local_ids = [i for i, d in enumerate(devices)
                 if d.process_index == jax.process_index()]
    if not local_ids:
        return slice(0, 0)
    lo, hi = min(local_ids), max(local_ids) + 1
    return slice(lo * per_device, hi * per_device)


def distribute_client_batch(packed: ClientBatch, mesh) -> dict:
    """Assemble the global client-sharded batch from per-process local rows.

    Single-process: equivalent to a plain device_put with the client sharding.
    Multi-process: each process contributes only its local slice, avoiding
    the reference's everyone-loads-everything redundancy (SURVEY.md §3.1).
    """
    shard = client_sharding(mesh)
    c = packed.num_clients
    if jax.process_count() == 1:
        return {
            "x": jax.device_put(packed.x, shard),
            "y": jax.device_put(packed.y, shard),
            "mask": jax.device_put(packed.mask, shard),
        }
    sl = local_client_slice(c, mesh)

    def put(arr: np.ndarray):
        return jax.make_array_from_process_local_data(shard, arr[sl],
                                                      arr.shape)

    return {"x": put(packed.x), "y": put(packed.y), "mask": put(packed.mask)}
