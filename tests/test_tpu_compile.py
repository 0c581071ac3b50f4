"""Compile-only checks of the main device programs for a described v5e
chip (no chip attached: the TPU compiler refuses here what the chip would
refuse, at no chip time).

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and the xdist workers must all collect the
same tests. The persistent compile cache is off around these compiles — a
described-topology executable is written to it but cannot be read back
without a chip."""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from automerge_tpu.tpu import paging  # noqa: E402
from automerge_tpu.tpu import pallas_kernels as pk  # noqa: E402
from automerge_tpu.tpu.engine import ChangeOpsBatch  # noqa: E402

# chip_smoke.py's farm: 8192 docs x 8 rounds x 64 ops (working width 512,
# page 64); the slab is sized exactly as BatchedMapEngine sizes it
PAGE, WIDTH, OPS = 64, 512, 64
SLAB_ROWS = 8192 * WIDTH
DOCS = 256  # active-doc bucket: keeps each compile to a few seconds
ACTORS = 64
# the smoke's Bloom check: 32 channels x 2048-change histories x 1024
# candidates (two word tiles, eight entry tiles, four query tiles)
FILTERS, ENTRIES, QUERIES = 32, 2048, 1024
NUM_WORDS = (ENTRIES * 10 + 31) // 32
LEB_BYTES = 1 << 16


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2, with the
    persistent compile cache off for the module."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _slab(s):
    return paging.SlabState(
        key=s((SLAB_ROWS,), jnp.int32), op=s((SLAB_ROWS,), jnp.int64),
        action=s((SLAB_ROWS,), jnp.int32), value=s((SLAB_ROWS,), jnp.int64),
        pred=s((SLAB_ROWS,), jnp.int64), overwritten=s((SLAB_ROWS,), jnp.bool_),
    )


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, f"{used / 1e9:.2f} GB does not fit a v5e chip"


def test_paged_apply_ops_compiles(chip):
    s = _spec(chip)
    pages = s((DOCS, WIDTH // PAGE), jnp.int32)
    changes = ChangeOpsBatch(
        key=s((DOCS, OPS), jnp.int32), op=s((DOCS, OPS), jnp.int64),
        action=s((DOCS, OPS), jnp.int32), value=s((DOCS, OPS), jnp.int64),
        pred=s((DOCS, OPS), jnp.int64),
    )
    compiled = paging.paged_apply_ops.fn.lower(
        _slab(s), pages, changes, pages, page_size=PAGE
    ).compile()
    _fits_one_chip(compiled)


def test_paged_visible_ranked_compiles(chip):
    s = _spec(chip)
    compiled = paging.paged_visible_ranked.fn.lower(
        _slab(s), s((DOCS, WIDTH // PAGE), jnp.int32),
        s((ACTORS,), jnp.int32), page_size=PAGE,
    ).compile()
    _fits_one_chip(compiled)


def test_patch_column_rows_compiles(chip):
    s = _spec(chip)
    rows = DOCS * OPS
    compiled = paging.patch_column_rows.fn.lower(
        s((DOCS, WIDTH), jnp.bool_), s((DOCS, WIDTH), jnp.int64),
        s((DOCS, WIDTH), jnp.int64), s((ACTORS,), jnp.int32),
        s((rows,), jnp.int64), s((rows,), jnp.int64),
    ).compile()
    _fits_one_chip(compiled)


def _lower_kernel(name, s):
    if name == "bloom_build":
        return pk.bloom_build.fn.lower(
            s((FILTERS, ENTRIES, 3), jnp.uint32), s((FILTERS,), jnp.int32),
            NUM_WORDS, interpret=False,
        )
    if name == "bloom_query":
        return pk.bloom_query.fn.lower(
            s((FILTERS, NUM_WORDS), jnp.uint32), s((FILTERS,), jnp.int32),
            s((FILTERS,), jnp.int32), s((FILTERS, QUERIES, 3), jnp.uint32),
            interpret=False,
        )
    # decode.leb128_scan_device's kernel over a 64k-byte stream of
    # 4-byte varints: 8 seven-bit planes per byte
    return pk.leb128_segment_sum.fn.lower(
        s((LEB_BYTES, 8), jnp.float32), s((LEB_BYTES,), jnp.int32),
        LEB_BYTES // 4, interpret=False,
    )


@pytest.mark.parametrize(
    "name", ["bloom_build", "bloom_query", "leb128_segment_sum"]
)
def test_pallas_kernel_compiles(chip, name):
    compiled = _lower_kernel(name, _spec(chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
