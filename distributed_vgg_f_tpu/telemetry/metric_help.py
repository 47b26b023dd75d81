"""One registry of metric-family help text — the source for every
`# HELP` line the Prometheus surfaces emit (the per-process exporter's
/metrics AND the fleet collector's aggregate /metrics).

The table is keyed by NAMESPACE (the `<subsystem>/` prefix of the
registry's `<subsystem>/<metric>` names), not per-metric: per-metric prose
already lives in the README "Counter namespace" table, and duplicating it
here would rot. `help_for(name)` renders the family line a scraper shows
next to the counter.

Lint contract (tools/lint/rules.py counter-namespace-drift): the keys of
NAMESPACE_HELP must equal the namespaces of the README counter table —
a counter namespace that ships without help text (or help text for a
namespace nothing registers) fails `tools/check.sh`. The `bench/`
namespace is excluded on both sides (bench-only, never in training runs).

Leaf module by the telemetry import contract: stdlib only, imports
nothing from the package.
"""

from __future__ import annotations

#: namespace → one-line help text. Keep entries terse: Prometheus shows
#: them inline in the exposition; the README table carries the detail.
NAMESPACE_HELP = {
    "decode": "native JPEG decoder stats (images, phase times, restart "
              "entropy path, scale histogram)",
    "prefetch": "device-prefetch pipeline (batches, waits, queue depths, "
                "snapshot cache, bytes in flight)",
    "native_loader": "native batch-loader iterator",
    "resilience": "non-finite guards and the data-stall watchdog",
    "checkpoint": "checkpoint manager (saves, retries, waits, restores)",
    "fault": "chaos injectors (injected nan/stall/crash/preempt/kills)",
    "step": "jitted train-step dispatch wrapper",
    "moe": "language-model expert routing (assignments held, expert "
           "load extremes, dropped assignments, passes over the routed "
           "buffers and the rows they hold, the share of tokens the "
           "router's group limit lets reach this share)",
    "ssm": "language-model state-space layers (chunks scanned a step, "
           "smallest decay of any layer, layers on the Pallas kernels)",
    "kda": "language-model delta-rule linear-attention layers (chunks a "
           "step, smallest decay of any layer, layers whose recurrence "
           "and whose short convolutions are on the Pallas kernels)",
    "compile": "JAX's own compile events (traces, lowerings, backend "
               "compiles, persistent-cache reads, hits and misses)",
    "startup": "where set-up starts (the process's start on the span "
               "ring's clock)",
    "eval": "trainer evaluation passes",
    "distributed": "cross-process coordination barriers",
    "telemetry": "the telemetry registry itself (poller faults)",
    "exporter": "per-process live HTTP observability endpoint",
    "autotune": "closed-loop ingest/admission controller (windows, "
                "actuations, rails, per-knob gauges)",
    "augment": "fused on-device augmentation stage",
    "comm": "gradient/parameter exchange (collective payload bytes, "
            "buckets, ZeRO gathers)",
    "ingest_service": "disaggregated ingest (worker serving plane + "
                      "trainer-side client)",
    "serving": "predict server (admission, sheds, batches, latency "
               "quantiles, per-tier traffic + quantiles)",
    "ingest_state": "position-exact resumable ingest (state blobs, "
                    "transplants, live rebuilds)",
    "elastic": "live elastic resize (survivor-mesh resizes, shard "
               "evacuations, downtime)",
    "collector": "fleet collector scrape loop (scrapes, faults, endpoint "
                 "liveness)",
    "fleet": "fleet-level aggregation (merged windows, live processes, "
             "stragglers)",
}


def help_for(name: str) -> str:
    """Family help line for one registry metric name. Unknown namespaces
    (dynamic/bench-only) get a generic line rather than an error — the
    exporter must render whatever the registry holds."""
    ns = name.split("/", 1)[0]
    return NAMESPACE_HELP.get(ns, f"{ns} subsystem metric")
