"""Ask the TPU's compiler, without a TPU: the main path's Pallas kernels at
real widths, compiled for a described (not attached) v5e chip.

Interpret-mode tests cannot see what Mosaic refuses — a misaligned slice,
or more scoped VMEM than a kernel may use (the 128-key comb stack needed
18.5 MB against the 16 MiB default).  These compiles can, at no chip
time.  The XLA and BLS compiles (minutes each) stay in the builder's
scratch script.

The topology is described in a fixture, never at import: only one process
may load the TPU's library, and every xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from smartbft_tpu.crypto import p256, pallas_comb, pallas_ecdsa, pallas_ed25519

#: the n=64 cluster's top pad rung (64 replicas x 42 votes), and one
#: 128-lane tile — the single-grid-step shape every rung <= 128 pads to
WAVE_LANES, TILE_LANES = 2688, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: the next run would warn and
    compile again.  Keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles(fn, one_chip, shapes, **static):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not a fallback
    return text


def _lanes(lanes, operands):
    """The comb kernels' per-lane byte operands ((B, 32) uint8 each)."""
    return [((lanes, 32), jnp.uint8)] * operands


def _tables(nkeys):
    """Base-point table + the stack of ``nkeys`` key tables."""
    return [((pallas_comb.ROWS, pallas_comb.TSIZE), jnp.bfloat16),
            ((nkeys * pallas_comb.ROWS, pallas_comb.TSIZE), jnp.bfloat16)]


@pytest.mark.parametrize("lanes,nkeys", [
    (WAVE_LANES, 64),                                   # the n=64 committee
    (TILE_LANES, pallas_comb.CombKeyRegistry().cap),    # every key it admits
], ids=["n64-wave", "cap-keys"])
def test_comb_p256_compiles_for_v5e(one_chip, lanes, nkeys):
    shapes = _lanes(lanes, 3) + [((lanes,), jnp.int32)] + _tables(nkeys)
    text = _compiles(pallas_comb.ecdsa_verify_comb, one_chip, shapes, tile=128)
    # the two names the benchmark's device readers depend on: the XLA
    # module's (comb_us_per_sig matches "comb" in it) and the custom
    # call's (the ledger's device_ops breakdown, and the check that every
    # such device event lies inside a tpubft.verify.device span)
    module = text.split("\n", 1)[0]
    assert module.startswith("HloModule") and "comb" in module, module
    assert any("custom-call" in line and "ecdsa_verify_comb" in line
               for line in text.splitlines())


def test_comb_p256_compiles_for_a_four_chip_mesh(topo):
    """`mesh4-n16-p256`'s one rung: 4 x 128 lanes over the 16 replicas'
    keys, the comb kernel under shard_map on every chip of a v5e-4 host.
    Lanes in and mask out are split four ways, the tables are whole on
    each chip, and the compiler puts in no collective."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices[:4]), ("batch",))
    lane, whole = NamedSharding(mesh, P("batch")), NamedSharding(mesh, P())
    lanes, nkeys = 4 * TILE_LANES, 16
    shapes = [((lanes, pallas_comb.MESH_LANE_BYTES), jnp.uint8)] \
        + _tables(nkeys)
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=whole if i else lane)
            for i, (shape, dtype) in enumerate(shapes)]
    compiled = pallas_comb.mesh_comb_launcher(mesh).lower(*args).compile()
    text = compiled.as_text()
    module = text.split("\n", 1)[0]
    assert "comb" in module, module  # what comb_us_per_sig matches
    assert any("custom-call" in line and "ecdsa_verify_comb" in line
               for line in text.splitlines())
    assert not any(op in text for op in
                   ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute"))
    out, = compiled.output_shardings if isinstance(
        compiled.output_shardings, (list, tuple)) \
        else (compiled.output_shardings,)
    assert len(out.device_set) == 4 and out.spec == P("batch")


def test_comb_ed25519_compiles_for_v5e(one_chip):
    shapes = (_lanes(WAVE_LANES, 4)
              + [((WAVE_LANES,), jnp.uint32),  # host pre-check mask
                 ((WAVE_LANES,), jnp.int32)]   # key index
              + _tables(64))
    _compiles(pallas_ed25519.eddsa_verify_comb, one_chip, shapes, tile=128)


def test_generic_pallas_p256_compiles_for_v5e(one_chip):
    limbs = ((WAVE_LANES, p256.NLIMBS), jnp.uint32)
    _compiles(pallas_ecdsa.ecdsa_verify, one_chip, [limbs] * 5, tile=128)


def test_arbitrary_key_ed25519_compiles_for_v5e(one_chip):
    """The envelope rung (a block of 500 -> 512 lanes): five limb operands
    and the host mask; the module keeps the name its device reader
    matches (``ed25519_us_per_sig``: ``jit_ed25519_verify`` exactly)."""
    lanes = 512
    shapes = [((lanes, pallas_ed25519.NL), jnp.uint32)] * 5 \
        + [((lanes,), jnp.uint32)]
    text = _compiles(pallas_ed25519.ed25519_verify, one_chip, shapes,
                     tile=pallas_ed25519.TILE)
    module = text.split("\n", 1)[0]
    assert module.split()[1].rstrip(",") == "jit_ed25519_verify", module
