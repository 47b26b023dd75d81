"""Multi-host runtime initialization.

Reference equivalent (SURVEY.md §3.2): MPI_Init / tf.train.Server role dispatch.
On TPU all hosts are symmetric SPMD workers: `jax.distributed.initialize()` wires
the coordination service; afterwards `jax.devices()` spans every chip in the slice
and meshes built over it ride ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import glob
import json
import logging
import os

import jax

from distributed_vgg_f_tpu import telemetry

log = logging.getLogger(__name__)


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Initialize the JAX distributed runtime when running multi-host.

    No-op when single-process (the common case on this machine, and in tests).
    On Cloud TPU VMs, `jax.distributed.initialize()` with no arguments
    auto-discovers the cluster from the TPU metadata — the moral equivalent of
    `mpirun` wiring up ranks in the reference.
    """
    explicit = coordinator_address is not None
    auto = any(os.environ.get(v) for v in
               ("MEGASCALE_COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"))
    if not (explicit or auto):
        # IMPORTANT: return without touching jax at all — even
        # jax.process_count() initializes the XLA backend, after which
        # jax.distributed.initialize refuses to run (caught by
        # tests/test_multihost.py).
        log.info("single-process run; skipping jax.distributed.initialize")
        return
    kwargs = {}
    if explicit:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # Already initialized (e.g. the Trainer's no-arg call after the CLI
        # already wired the cluster), or backend already up in a
        # single-process tool — proceed rather than abort.
        log.warning("jax.distributed.initialize skipped: %s", e)
        return
    log.info("distributed initialized: process %d/%d, %d local / %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())


def coordination_barrier(tag: str, *, timeout_ms: int = 600_000) -> bool:
    """Align every process at a named barrier via the coordination service —
    plain gRPC to the coordinator, NOT a device collective.

    Why it exists: the first collective execution of a run triggers Gloo's
    TCP rendezvous, which has a fixed ~30 s key-value deadline, while ranks
    can reach that first collective with much larger skew (per-rank dataset
    build, tracing, contended-host compilation — observed >30 s on this
    1-vCPU box with 4 ranks, failing Gloo context init with
    DEADLINE_EXCEEDED). This barrier carries an explicit long timeout, so
    aligning on it first keeps the subsequent rendezvous skew to
    milliseconds. Returns False (no-op) when single-process or no
    coordination client is wired.
    """
    from jax._src import distributed as _dist  # no public barrier API
    client = getattr(_dist.global_state, "client", None)
    if client is None:
        return False
    # "coord" span: barrier wait time IS the inter-rank skew — on the trace
    # it shows which rank the others were waiting for.
    with telemetry.span(f"barrier_{tag}", "coord"):
        client.wait_at_barrier(f"dvggf_{tag}", timeout_ms)
    telemetry.inc("distributed/barriers")
    return True


# ---------------------------------------------------------------------------
# Telemetry sidecars: per-process JSONL, process 0 aggregates.
# ---------------------------------------------------------------------------

def telemetry_sidecar_path(base_dir: str, prefix: str = "telemetry") -> str:
    """This process's telemetry sidecar file. One file per process — hosts
    never contend on a shared writer; the rank is in the name so the
    aggregate (and a human) can attribute counters to hosts."""
    return os.path.join(base_dir, f"{prefix}_p{jax.process_index():05d}.jsonl")


def write_telemetry_sidecar(base_dir: str, record: dict,
                            prefix: str = "telemetry") -> str:
    """Append one JSON record (registry snapshot + span stats, stamped with
    the process index) to this process's sidecar. Returns the path."""
    os.makedirs(base_dir, exist_ok=True)
    path = telemetry_sidecar_path(base_dir, prefix)
    with open(path, "a", buffering=1) as f:
        f.write(json.dumps({"process": jax.process_index(), **record},
                           allow_nan=False) + "\n")
    return path


def aggregate_telemetry_sidecars(base_dir: str,
                                 prefix: str = "telemetry",
                                 expected_processes: int | None = None,
                                 ) -> dict:
    """Process-0 aggregation over every sidecar present (shared filesystem,
    the same contract Orbax relies on): COUNTERS summed across processes;
    GAUGES kept per-rank (summing instantaneous values — four ranks'
    queue_depth=2 → "8" — would fabricate a number nobody measured).
    Best-effort by design — a crashed rank's missing sidecar degrades the
    aggregate instead of hanging the survivors.

    `expected_processes` (the live run passes jax.process_count()) caps the
    rank range: a run reusing a sidecar_dir left by a LARGER previous run
    must not fold the stale ranks' files into its own aggregate (the
    current ranks' files are append-mode, so taking each file's LAST
    record already excludes their old runs). Offline analysis of a
    finished run's directory omits it and reads every rank."""
    processes = {}
    counters: dict = {}
    gauges: dict = {}
    for path in sorted(glob.glob(
            os.path.join(base_dir, f"{prefix}_p*.jsonl"))):
        if expected_processes is not None:
            try:
                rank = int(os.path.basename(path)[len(prefix) + 2:-6])
            except ValueError:
                continue
            if rank >= expected_processes:
                continue  # stale sidecar from a larger previous run
        last = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        last = json.loads(line)
                    except ValueError:
                        continue  # torn tail write from a dying rank
        if last is None:
            continue
        proc = int(last.get("process", -1))
        processes[proc] = os.path.basename(path)
        for name, value in (last.get("counters") or {}).items():
            if isinstance(value, (int, float)):
                counters[name] = counters.get(name, 0) + value
        for name, value in (last.get("gauges") or {}).items():
            if isinstance(value, (int, float)):
                gauges.setdefault(name, {})[str(proc)] = value
    return {"processes": len(processes), "counters": counters,
            "gauges_by_process": gauges,
            "sidecars": [processes[p] for p in sorted(processes)]}
