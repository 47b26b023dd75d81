"""Which device a measurement ran on — and refusing the wrong one.

A measurement path that meant the chip and got the CPU must fail, not
publish a CPU number under a device metric's name. JAX falls back to the
CPU with only a warning when no accelerator answers, so the benches ask
here first. The CPU is allowed only when it was asked for by name
(`JAX_PLATFORMS=cpu`: tests and rehearsals), and every result line carries
`device_facts()` so a reader can tell the two apart.
"""

from __future__ import annotations

import os


class NoAcceleratorError(RuntimeError):
    """The run meant an accelerator and JAX found none."""


def cpu_requested() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def device_facts() -> dict:
    """platform / device_kind / device_count as JAX reports them — the
    fields every benchmark result line names."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_accelerator() -> dict:
    """`device_facts()`, or NoAcceleratorError when the platform is not
    `tpu` and the CPU was not explicitly requested."""
    facts = device_facts()
    if facts["platform"] != "tpu" and not cpu_requested():
        raise NoAcceleratorError(
            f"JAX found platform {facts['platform']!r} "
            f"({facts['device_kind']}), not 'tpu'. A measurement does not "
            "fall back to another device; set JAX_PLATFORMS=cpu to ask for "
            "the CPU by name.")
    return facts
