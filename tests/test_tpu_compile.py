"""Compile the cache's device programs for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes one and the
installed TPU compiler builds for it, which catches what interpret-mode
Pallas cannot (tiling, on-chip memory, lowering). Shapes are those of
``chip_smoke.py``: the msr2 cache at 10 % capacity (sketch width 16384,
Main grown to the sketch width, a 512-slot window) with chunk 256.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers all import this
file.
"""

import os

import pytest

CAP = 345_000_000  # ~10 % of the msr2 trace's object bytes
EE = 8600  # expected resident objects: sketch width 16384
WIDTH = 16384
SLOTS = 16384  # Main slots, grown straight to the sketch width
WSLOTS = 512
PAD = 256  # scan length of a chunk-256 plane
LANES = 21  # one lane per admission x main combo
SPECS = {"slru": "wtlfu-av-slru", "sampled": "wtlfu-qv-sampled_frequency_size"}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, dtype or jnp.int32, sharding=sharding)


def _chunk_program(sharding, main_kind, lanes=None):
    """Arg stand-ins and statics of a smoke-sized ``device_full`` plane's
    chunk program, as its own ``chunk_args`` lays them out (``lanes`` adds
    a fleet bucket's lane axis), compiled Pallas."""
    import jax

    from repro.core import REGISTRY

    pol = REGISTRY.build(f"{SPECS[main_kind]}?data_plane=device_full&chunk={PAD}",
                         CAP, expected_entries=EE,
                         sketch_kwargs={"use_pallas": True})
    pipe = pol._device_pipeline
    assert (pipe.sketch.width, pipe.scan_len()) == (WIDTH, PAD)
    pipe.mirror.upload([], [], pipe.sampled, PAD)
    pipe.mirror.grow(SLOTS, WSLOTS)
    lead = () if lanes is None else (lanes,)
    args = [jax.ShapeDtypeStruct(lead + a.shape, a.dtype, sharding=sharding)
            for a in pipe.chunk_args(pol)]
    return args, {**pipe._statics(pol), "interpret": False}


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "Pallas kernel missing"


def test_fused_cms_kernel(one_chip):
    """The CMS flush of the chunk scan (one key: the in-place increment)
    and of a padded pending batch (the width sweep)."""
    import jax

    from repro.kernels.cms.ops import flush_scores
    from repro.kernels.cms.ref import ROWS

    def step(table, upd, n, est):
        return flush_scores(table, upd, n, est, cap=15, use_pallas=True,
                            interpret=False)

    for n_keys, name in ((1, "cms_update_one"), (PAD, "cms_update_estimate")):
        compiled = jax.jit(step).lower(
            _sds(one_chip, (ROWS, WIDTH)), _sds(one_chip, (n_keys,)),
            _sds(one_chip, ()), _sds(one_chip, (1,))).compile()
        _assert_kernel(compiled)
        assert f"%{name}" in compiled.as_text(), f"{name} missing"


def _kernel_payloads(lowered_text):
    """The serialized Mosaic modules of the ``tpu_custom_call``s."""
    import base64
    import json
    import re

    for cfg in re.findall(r'backend_config = "(\{\\22custom_call_config.*?)"',
                          lowered_text):
        body = json.loads(cfg.replace("\\22", '"'))["custom_call_config"]["body"]
        yield base64.b64decode(body)


def test_kernel_payload_free_of_checkout_path(one_chip):
    """The persistent cache keys on the kernel payload, which records the
    kernel's source path: ``source_path_regex`` (set by the entry points'
    ``use_compile_cache``) makes it relative to the checkout, so a checkout
    at another path finds the same compiled programs."""
    import pathlib

    import jax

    from repro.compile_cache import source_path_regex
    from repro.kernels.cms.ops import flush_scores
    from repro.kernels.cms.ref import ROWS

    root = pathlib.Path(__file__).resolve().parents[1]
    flag = "jax_hlo_source_file_canonicalization_regex"
    old = getattr(jax.config, flag)

    def payloads():
        def step(table, upd, n, est):
            return flush_scores(table, upd, n, est, cap=15, use_pallas=True,
                                interpret=False)

        text = jax.jit(step).lower(
            _sds(one_chip, (ROWS, 128)), _sds(one_chip, (1,)),
            _sds(one_chip, ()), _sds(one_chip, (1,))).as_text()
        return list(_kernel_payloads(text))

    try:
        jax.config.update(flag, None)
        raw = payloads()
        jax.config.update(flag, source_path_regex(root))
        canon = payloads()
    finally:
        jax.config.update(flag, old)
    here = str(root).encode()
    assert raw and all(here in p for p in raw)
    assert canon and not any(here in p for p in canon)
    assert all(b"src/repro/kernels/cms/cms.py" in p for p in canon)


@pytest.mark.parametrize("main_kind", [
    pytest.param("slru", id="slru-av-frequency"),
    pytest.param("sampled", id="sampled-qv-frequency_size"),
])
def test_simulate_chunk(one_chip, main_kind):
    """The chunk program; its CMS kernel is an instruction of its own name
    that returns the table, which a chip trace names the kernel's events
    by, and the table is updated in place: no copy of it is made."""
    import re

    from repro.kernels.cms.ref import ROWS
    from repro.kernels.device_full import _simulate_chunk

    args, statics = _chunk_program(one_chip, main_kind)
    compiled = _simulate_chunk.lower(*args, **statics).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    table = re.escape(f"s32[{ROWS},{WIDTH}]") + r"\{[^}]*\}"
    named = (rf'%cms_update_one(\.\d+)? = {table} custom-call\(.*'
             r'custom_call_target="tpu_custom_call"')
    assert re.search(named, text), "one-key CMS kernel not named"
    assert not re.search(rf"= {table} copy\(", text), "sketch table copied"


def test_decision_chunk(one_chip):
    """The per-decision program of the host path over a sampled main
    (``_decide_sampled``: mirror scatter, CMS flush, sample walk)."""
    import jax.numpy as jnp

    from repro.kernels.admission import _WRITE_PAD, _decide_sampled
    from repro.kernels.cms.ref import ROWS

    def s(*shape, dtype=None):
        return _sds(one_chip, shape, dtype)

    pad = (s(_WRITE_PAD), s(_WRITE_PAD), s(_WRITE_PAD))
    compiled = _decide_sampled.lower(
        s(ROWS, WIDTH), s(SLOTS), s(SLOTS), *pad, s(16), s(), s(), s(), s(),
        s(dtype=jnp.uint32), s(dtype=jnp.uint32),
        discipline="qv", rule="frequency_size", sample=5, early_pruning=True,
        cap=15, use_pallas=True, interpret=False).compile()
    _assert_kernel(compiled)


def test_fleet_chunk_21_lanes(one_chip):
    from repro.kernels.fleet import _fleet_chunk

    args, statics = _chunk_program(one_chip, "sampled", lanes=LANES)
    compiled = _fleet_chunk.lower(*args, **statics).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 << 30
