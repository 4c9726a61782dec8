"""Distributed machinery tests: sharding rules (divisibility guards, rule
coverage), the roofline HLO analyzer, and a small-mesh lowering smoke test
run in a subprocess (device count must be set before jax init)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.roofline import HloAnalysis, _shape_bytes, roofline_terms


class TestShapeParsing:
    def test_simple(self):
        assert _shape_bytes("bf16[2,3]{1,0}") == 12
        assert _shape_bytes("f32[10]") == 40
        assert _shape_bytes("pred[4,4]") == 16
        assert _shape_bytes("s32[]") == 4

    def test_tuple(self):
        assert _shape_bytes("(f32[2], s32[4])") == 8 + 16

    def test_tuple_with_index_comments(self):
        s = "(s32[], bf16[8,64]{1,0}, /*index=5*/pred[8]{0})"
        assert _shape_bytes(s) == 4 + 8 * 64 * 2 + 8


SAMPLE_HLO = textwrap.dedent(
    """
    HloModule test

    %cond (p: (s32[], f32[8,8])) -> pred[] {
      %p = (s32[], f32[8,8]{1,0}) parameter(0)
      %c = s32[] constant(5)
      %i = s32[] get-tuple-element(%p), index=0
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
      %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups=[2,4]<=[8], to_apply=%add_comp
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%ni, %ar)
    }

    ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
      %a = f32[8,8]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,8]{1,0}) tuple(%zero, %a)
      ROOT %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body
    }
    """
)


class TestHloAnalysis:
    def test_trip_count_multiplies(self):
        ana = HloAnalysis(SAMPLE_HLO)
        # dot: 2*8*8*8 = 1024 flops, x5 loop trips
        assert ana.flops() == 1024 * 5

    def test_collective_bytes_with_groups(self):
        ana = HloAnalysis(SAMPLE_HLO)
        # all-reduce of 256B in groups of 4: 2*256*(3/4) = 384 per trip, x5
        assert ana.collective_bytes() == pytest.approx(384 * 5)

    def test_roofline_terms_structure(self):
        t = roofline_terms(SAMPLE_HLO)
        assert t["dominant"] in ("compute", "memory", "collective")
        assert t["step_s_lower_bound"] > 0
        assert t["collective_counts"] == {"all-reduce": 1}


class TestShardingRules:
    def test_divisibility_guard(self):
        from repro.distributed.sharding import guard

        mesh = jax.make_mesh((1,), ("model",))
        # dims not divisible by axis size are replicated
        assert guard((9, 4), P("model", None), mesh) == P("model", None)

    def test_param_specs_cover_all_archs(self):
        """Every leaf of every arch must get a spec (no rule gaps)."""
        from repro.configs import ARCHS, get_config
        from repro.distributed.sharding import param_specs
        from repro.models import LM

        mesh = jax.make_mesh((1,), ("model",))
        for arch in ARCHS:
            cfg = get_config(arch).scaled_down()
            model = LM(cfg, dtype=jnp.float32, remat=False)
            shapes = model.abstract_params()
            specs = param_specs(shapes, mesh)
            n_leaves = len(jax.tree.leaves(shapes))
            n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
            assert n_specs == n_leaves, arch


SUBPROC_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.dryrun import lower_cell
rec = lower_cell("{arch}", "{shape}")
assert rec["memory"]["peak_gib"] > 0
assert rec["roofline"]["hlo_flops_per_chip"] > 0
print("SUBPROC_OK", rec["roofline"]["dominant"])
"""


@pytest.mark.slow
class TestSmallMeshLowering:
    """Full dry-run path on 8 fake devices (subprocess: device count must be
    fixed before jax initializes). Uses the production 16x16 mesh path via
    512 devices only in the real dry-run; here we just prove the machinery
    end-to-end per step kind."""

    @pytest.mark.parametrize("arch,shape", [
        ("smollm-135m", "train_4k"),
        ("smollm-135m", "decode_32k"),
    ])
    def test_lower_cell_subprocess(self, arch, shape):
        script = (
            'import os\n'
            'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"\n'
            'import sys\n'
            'sys.path.insert(0, "src")\n'
            'from repro.launch.dryrun import lower_cell\n'
            f'rec = lower_cell("{arch}", "{shape}")\n'
            'assert rec["memory"]["peak_gib"] > 0\n'
            'assert rec["roofline"]["hlo_flops_per_chip"] > 0\n'
            'print("SUBPROC_OK", rec["roofline"]["dominant"])\n'
        )
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=560, cwd=Path(__file__).resolve().parents[1],
        )
        assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
