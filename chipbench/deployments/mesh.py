"""One orderer host with several chips: the default deployment behind a
``MeshVerifyEngine`` as wide as the cell's ``chips``.

Built on the default deployment (``deployments/sharded.py``): the same
``ShardedCluster(shards=1, crypto="p256")``, the same coalescer, and the
engine ``sharded`` already builds for ``"engine": "mesh"`` at the cell's
width, on the program's own ladder (``engine.pad_sizes``: what
``crypto.ladder.auto_pad_sizes`` gives, rounded by the engine to what its
kernel can launch).  The configuration states
``verify_mesh_devices`` / ``verify_mesh_topology``; the program's
``CryptoProvider.configure_verify_mesh`` then finds the mesh installed and
swaps nothing in after the ladder was prewarmed.

What this file adds is the deployment's own part of ``correct``
(:meth:`Deployment.reference_faults`; it adds reasons and never removes
one): that what served the window WAS the mesh the configuration states.
The harness's gates already hold every launch to ``expected_kernel``, the
breaker closed, no host fallback, no downgrade, no compile; the plain
reference of the verdicts is the default deployment's (OpenSSL, lane by
lane, on the set-up wave, through the mesh).

It needs a program whose mesh engine counts the launches it laid out over
fewer devices than it was built with; a program without that count (any
before PR 32, whose mesh could not run the comb kernel either) is refused
at once, before JAX is touched.
"""

from __future__ import annotations

import json

from chipbench import deploy

sharded = deploy.load_deployment("sharded")

CONFIG_KEYS = sharded.CONFIG_KEYS
WORKLOAD_KEYS = sharded.WORKLOAD_KEYS

refuse = sharded.refuse


class Deployment(sharded.Deployment):

    def check(self) -> None:
        super().check()
        from smartbft_tpu.crypto.provider import MeshVerifyStats

        if not hasattr(MeshVerifyStats, "launches_below_width"):
            refuse("this program's mesh engine does not count the launches "
                   "it laid out below its width "
                   "(MeshVerifyStats.launches_below_width): it cannot run "
                   "a mesh deployment")
        stated = self.config["configuration"].get("verify_mesh_devices")
        if stated != self.chips:
            refuse(f"the configuration states verify_mesh_devices={stated}, "
                   f"the cell runs on {self.chips} chip(s)")

    def reference_faults(self, ev: dict) -> list:
        """Reasons this deployment adds (never removes one)."""
        faults = []
        engine = self.coalescer.engine
        width = int(getattr(engine, "devices", 0))
        if type(engine).__name__ != "MeshVerifyEngine" or width != self.chips:
            faults.append(f"the engine behind the coalescer is a "
                          f"{type(engine).__name__} of {width or 1} "
                          f"device(s), not a MeshVerifyEngine of "
                          f"{self.chips}")
        mesh = ev["mesh"]
        if mesh.get("configured_devices") != self.chips:
            faults.append(f"the program was configured for a mesh of "
                          f"{mesh.get('configured_devices')} device(s), the "
                          f"cell runs on {self.chips}")
        below = mesh.get("launches_below_width")
        if below is None:
            faults.append("the engine does not say how many launches it "
                          "laid out below its width")
        elif below:
            faults.append(f"{below} launch(es) had inputs or output laid "
                          f"out over fewer than {self.chips} devices "
                          f"(last launch: {mesh.get('io_devices_last')})")
        print("chipbench: mesh: " + json.dumps(
            {k: mesh.get(k) for k in (
                "devices", "topology", "launches", "items", "slots",
                "fill_pct", "launches_spanning_all_devices",
                "launches_below_width", "io_devices_last",
                "capacity_items_per_launch")}), flush=True)
        return faults
