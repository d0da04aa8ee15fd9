"""Cross-pod sync schedules: measured HLO wire bytes, picsou vs ATA.

Lowers both schedules on a (2,4,4)-host mesh, parses the partitioned HLO
and reports collective wire bytes + the analytic DCN split for the
production (2,16,16) mesh. This is the paper's Figure-2 message-count
argument executed on real collectives.

The mesh needs 32 devices, which only the CPU backend can fake, so the
sweep runs as its own process and sets ``XLA_FLAGS`` before JAX is
imported:

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.bench_crosspod
"""

from __future__ import annotations

import os

HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=32"


def main():
    import jax
    import jax.numpy as jnp

    from repro.crosspod import (ata_cross_pod_sync, dcn_bytes_analytic,
                                picsou_cross_pod_sync)
    from repro.launch.mesh import make_mesh
    from repro.roofline.hlo_cost import analyze_hlo_text

    mesh = make_mesh((2, 4, 4), ("pod", "data", "model"))
    g = {"w": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)}
    n_bytes = 1024 * 1024 * 4

    rows = []
    for name, fn in (("picsou", picsou_cross_pod_sync),
                     ("ata", ata_cross_pod_sync)):
        lowered = jax.jit(lambda x, fn=fn: fn(x, mesh)).lower(g)
        hc = analyze_hlo_text(lowered.compile().as_text())
        rows.append((name, hc.wire_bytes, dict(hc.wire_by_kind)))

    print("# measured wire bytes per chip (1 sync of 4MB, mesh 2x4x4)")
    print("schedule,wire_bytes_per_chip,breakdown")
    for name, wire, kinds in rows:
        print(f"{name},{wire:.0f},"
              + ";".join(f"{k}={v:.0f}" for k, v in kinds.items()))

    print("# analytic DCN split on the production mesh (2,16,16)")
    print("schedule,dcn_bytes_per_chip,ici_bytes_per_chip,dcn_reduction")
    shape = {"pod": 2, "data": 16, "model": 16}
    for name in ("ata", "picsou"):
        d = dcn_bytes_analytic(n_bytes, shape, name)
        print(f"{name},{d['dcn_per_chip']:.0f},{d['ici_per_chip']:.0f},"
              f"{d.get('dcn_reduction', 1.0):.1f}")


if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + HOST_DEVICES_FLAG)
    main()
