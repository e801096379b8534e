"""The device a result was produced on, as JAX reports it.

Every entry point's output line carries these three keys, so a number
can never be read without the platform it came from (a CPU run is never
a device metric).
"""

from __future__ import annotations


def device_summary() -> dict:
  import jax
  devices = jax.devices()
  return {
      "platform": devices[0].platform,
      "device_kind": devices[0].device_kind,
      "device_count": len(devices),
  }
