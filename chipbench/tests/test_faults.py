"""The check refuses a run whose timed path is broken underneath. Each fault
is planted in the chunk program the window drives (one cache:
``_simulate_chunk``; shard caches: ``_fleet_chunk``), and the rest of a run
goes as usual, on the CPU at a small size:

* a step that returns its state unchanged;
* half of each launch's accesses left out, their answers left as misses;
* one answer altered where it is produced.

No cell exchanges anything between chips, so that fault has no place here.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import cells

CELLS = cells()


def _unchanged(real):
    from repro.kernels.device_full import _CARRY_FIELDS

    def program(*args, **kw):
        state = [jnp.array(a, copy=True) for a in args[:10]]
        scal, limbs = jnp.asarray(args[13]), jnp.asarray(args[14])
        outs = real(*args, **kw)
        return (*state, scal[..., :len(_CARRY_FIELDS)], limbs, outs[12])
    return program


def _half(real):
    from repro.kernels.device_full import _SCAL_IDX

    def program(*args, **kw):
        scal = np.array(args[13])
        scal[..., _SCAL_IDX["a_n"]] //= 2
        return real(*args[:13], jnp.asarray(scal), *args[14:], **kw)
    return program


def _altered(real):
    calls = []

    def program(*args, **kw):
        outs = real(*args, **kw)
        calls.append(1)
        if len(calls) == 3:
            hits = outs[12]
            outs = (*outs[:12], hits.at[..., 0].set(~hits[..., 0]))
        return outs
    return program


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.kernels import device_full, fleet

    monkeypatch.setattr(device_full, "_simulate_chunk", fault(device_full._simulate_chunk))
    monkeypatch.setattr(fleet, "_fleet_chunk", fault(fleet._fleet_chunk))
    result = harness.run(cell, 4242, 1.0, False, rehearse=True, scale=0.02,
                         log=lambda *a: None)
    assert result["correct"] is False
    assert result["checks"]["hit_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_same_run_unbroken_is_correct(cell):
    result = harness.run(cell, 4242, 1.0, False, rehearse=True, scale=0.02,
                         log=lambda *a: None)
    assert result["correct"] is True
    assert all(c["value"] == 0 for c in result["checks"].values())
