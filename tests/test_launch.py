"""Launch-layer tests: HLO collective parser, input specs, roofline math.
(The 512-device dry-run itself runs via launch/dryrun.py, not pytest.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, cells, get_config
from repro.launch import hlo_analysis
from repro.launch.dryrun import (_depth_overrides, _real_units, model_flops,
                                 n_params, roofline_terms)
from repro.launch.mesh import HW, parse_mesh
from repro.launch.specs import input_specs

jax.config.update("jax_platform_name", "cpu")


class TestHloParser:
    HLO = """
  %add.1 = f32[4,4] add(%a, %b)
  %ag = bf16[16,4096,128]{2,1,0} all-gather(%x), replica_groups={...}
  %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %rs = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) reduce-scatter(%p, %q)
  %cp = bf16[2,2]{1,0} collective-permute(%z)
  %a2a = f32[64,32]{1,0} all-to-all(%w)
"""

    def test_collective_bytes(self):
        st = hlo_analysis.collective_stats(self.HLO)
        assert st["all-gather"]["count"] == 1
        assert st["all-gather"]["bytes"] == 16 * 4096 * 128 * 2
        assert st["all-reduce"]["bytes"] == 1024 * 4
        assert st["reduce-scatter"]["bytes"] == 2 * 8 * 128 * 2
        assert st["collective-permute"]["bytes"] == 4 * 2
        assert st["all-to-all"]["bytes"] == 64 * 32 * 4
        total = sum(st[k]["bytes"] for k in hlo_analysis.COLLECTIVES)
        assert st["total_bytes"] == total

    def test_start_done_counted_once(self):
        hlo = """
  %s = bf16[8,8]{1,0} all-gather-start(%x)
  %d = bf16[8,8]{1,0} all-gather-done(%s)
"""
        st = hlo_analysis.collective_stats(hlo)
        assert st["all-gather"]["count"] == 1
        assert st["all-gather"]["bytes"] == 128

    def test_non_collective_ignored(self):
        st = hlo_analysis.collective_stats("%m = f32[4,4] dot(%a, %b)")
        assert st["total_bytes"] == 0


class TestInputSpecs:
    def test_all_cells_have_specs(self):
        for arch, shape in cells():
            cfg, kind, specs = input_specs(arch, shape)
            seq, batch, expect_kind = SHAPES[shape]
            assert kind == expect_kind
            if kind == "train":
                assert specs["tokens"].shape == (batch, seq)
                assert specs["labels"].shape == (batch, seq)
            elif kind == "prefill":
                assert specs["tokens"].shape == (batch, seq)
            else:
                tok, cache, t = specs
                assert tok.shape == (batch, 1)
                assert t.shape == ()
                assert len(jax.tree.leaves(cache)) > 0

    def test_vlm_and_audio_frontend_stubs(self):
        cfg, _, specs = input_specs("internvl2-1b", "train_4k")
        assert specs["patches"].shape == (256, cfg.n_patch_tokens,
                                          cfg.d_model)
        cfg, _, specs = input_specs("whisper-small", "train_4k")
        assert specs["frames"].shape == (256, cfg.encoder_len, cfg.d_model)

    def test_long_shape_only_for_subquadratic(self):
        cs = cells()
        long_archs = {a for a, s in cs if s == "long_500k"}
        assert long_archs == {"mamba2-2.7b", "recurrentgemma-9b"}
        # 10 archs x 3 shapes + 2 long cells
        assert len(cs) == 32

    def test_decode_cache_slots(self):
        cfg, _, (tok, cache, t) = input_specs("qwen3-32b", "decode_32k")
        k = cache["layers"]["k"]
        assert k.shape == (64, 128, 32768, 8, 128)
        # recurrentgemma long_500k: rolling window cache, not 512k slots
        cfg, _, (tok, cache, t) = input_specs("recurrentgemma-9b",
                                              "long_500k")
        attn_cache = cache["groups"]["pos2"]
        assert attn_cache["k"].shape[2] == cfg.window


class TestRooflineMath:
    def test_terms(self):
        res = {"flops": HW["peak_bf16_flops"],
               "bytes_accessed": HW["hbm_bw"] * 2,
               "collectives": {"total_bytes": HW["ici_bw"] * 3}}
        t = roofline_terms(res)
        assert t["t_compute"] == pytest.approx(1.0)
        assert t["t_memory"] == pytest.approx(2.0)
        assert t["t_collective"] == pytest.approx(3.0)
        assert t["bottleneck"] == "t_collective"

    def test_depth_overrides(self):
        cfg = get_config("recurrentgemma-9b")
        assert _real_units(cfg) == 12
        ov = _depth_overrides(cfg, 2)
        assert ov["n_layers"] == 2 * 3 + 2
        cfg = get_config("whisper-small")
        ov = _depth_overrides(cfg, 1)
        assert ov == {"n_layers": 1, "n_encoder_layers": 1}

    def test_param_counts_sane(self):
        c = n_params(get_config("qwen3-32b"))
        assert 30e9 < c["active_nonembed"] < 36e9
        c = n_params(get_config("granite-moe-1b-a400m"))
        assert c["active_nonembed"] < 0.8e9       # top-8/32 of experts
        assert c["total"] > 1.0e9

    def test_model_flops_train_vs_decode(self):
        f_train = model_flops(get_config("starcoder2-3b"), "train",
                              4096, 256)
        f_dec = model_flops(get_config("starcoder2-3b"), "decode",
                            32768, 128)
        assert f_train > f_dec * 1000


class TestParseMesh:
    @pytest.mark.parametrize("spec,n,want", [(None, 4, (4, 1)),
                                             ("1,4", 4, (1, 4)),
                                             ("2,4", 8, (2, 4))])
    def test_shapes(self, spec, n, want):
        assert parse_mesh(spec, n) == want

    @pytest.mark.parametrize("spec", ["2,4", "4", "1,2,2"])
    def test_rejects_wrong_device_count(self, spec):
        with pytest.raises(ValueError):
            parse_mesh(spec, 4)
