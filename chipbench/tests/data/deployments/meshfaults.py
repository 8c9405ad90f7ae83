"""Test-only: the mesh deployment over the program's toy signature scheme
(a device kernel that compiles in milliseconds, its plain reference
written out again in ``toyring``), with one thing broken underneath its
reference by ``"fault"``."""

from __future__ import annotations

from chipbench import deploy

mesh = deploy.load_deployment("mesh")
toyring = deploy.load_deployment("toyring")

CONFIG_KEYS = mesh.CONFIG_KEYS | {"fault"}
WORKLOAD_KEYS = mesh.WORKLOAD_KEYS


class Deployment(mesh.Deployment):

    def scheme(self):
        from smartbft_tpu.testing import toy_scheme

        return toy_scheme

    def reference_verdicts(self, items) -> list:
        return toyring.plain_verdicts(items)

    def pad_ladder(self) -> tuple:
        return (8, 64)

    def build(self, engine, wal_root: str) -> None:
        if self.config.get("fault") == "never_told":
            # the program is never told of the mesh, so it graduates
            # nothing: whatever engine was built stays behind the coalescer
            told = {k: v for k, v in self.config["configuration"].items()
                    if not k.startswith("verify_mesh")}
            self.config = dict(self.config, configuration=told)
        super().build(engine, wal_root)

    async def start(self) -> None:
        await super().start()
        if self.config.get("fault") == "narrow_launch":
            # every launch from here on is laid out over ONE device
            import jax

            engine = self.coalescer.engine
            engine._place = lambda a: jax.device_put(a, jax.devices()[0])
