"""Dry-run machinery: HLO census parser, cost probes, roofline analyzer."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.launch.dryrun import _shape_bytes, collective_census  # noqa: E402
import roofline  # noqa: E402


class TestShapeBytes:
    @pytest.mark.parametrize("s,want", [
        ("f32[128,4096]", 128 * 4096 * 4),
        ("bf16[2,3,4]", 24 * 2),
        ("pred[10]", 10),
        ("(f32[8], bf16[8])", 8 * 4 + 8 * 2),
        ("token[]", 0),
        ("f32[]", 4),   # scalar: empty dims → 1 elem... (documented: 4)
    ])
    def test_cases(self, s, want):
        assert _shape_bytes(s) == want


class TestCensus:
    HLO = """\
HloModule jit_step

%region_0.1 (a: f32[8]) -> f32[8] {
  ROOT %r = f32[8] add(%a, %a)
}

%while_body.5 (p: (f32[64,64], s32[])) -> (f32[64,64], s32[]) {
  %ar = f32[64,64] all-reduce(%x), replica_groups={}
  %cp = bf16[32,32] collective-permute(%y), source_target_pairs={{0,1}}
  ROOT %t = tuple(%ar)
}

ENTRY %main (p0: f32[128,128]) -> f32[128,128] {
  %ag = f32[128,128] all-gather(%p0), dimensions={0}
  %w = while(...), body=%while_body.5
  ROOT %done = f32[128,128] copy(%ag)
}
"""

    def test_buckets(self):
        c = collective_census(self.HLO)
        assert c["all-gather"]["count"] == 1
        assert c["all-gather"]["bytes"] == 128 * 128 * 4
        assert c["all-gather"]["loop_count"] == 0
        assert c["all-reduce"]["loop_count"] == 1
        assert c["all-reduce"]["loop_bytes"] == 64 * 64 * 4
        assert c["collective-permute"]["loop_count"] == 1
        assert c["collective-permute"]["loop_bytes"] == 32 * 32 * 2


class TestRooflineAnalyzer:
    def _rec(self, **over):
        rec = {
            "arch": "a", "shape": "s", "mesh": "single", "kind": "train",
            "status": "ok",
            "meta": {"scan_trip": 4, "model_flops": 1e12},
            "cost": {"flops": 1e9, "bytes accessed": 1e9},
            "probe": {
                "0": {"flops": 2e8, "bytes": 1e8},
                "1": {"flops": 4e8, "bytes": 3e8},
            },
            "collectives": {
                "all-reduce": {"count": 1, "bytes": 1e6,
                               "loop_count": 2, "loop_bytes": 5e5},
            },
            "memory": {"temp_size_in_bytes": int(1e9),
                       "argument_size_in_bytes": int(1e8)},
        }
        rec.update(over)
        return rec

    def test_probe_extrapolation(self):
        a = roofline.analyze(self._rec())
        # f(L) = f0 + L*(f1-f0) = 2e8 + 4*2e8 = 1e9
        assert a["flops_per_device"] == pytest.approx(1e9)
        assert a["hbm_bytes_per_device"] == pytest.approx(1e8 + 4 * 2e8)

    def test_collective_loop_multiplier(self):
        a = roofline.analyze(self._rec())
        # 1e6 top + 4 trips × 5e5 loop = 3e6
        assert a["collective_bytes_per_device"] == pytest.approx(3e6)

    def test_terms_and_bottleneck(self):
        a = roofline.analyze(self._rec())
        peak = roofline.PEAKS[roofline.DRYRUN_TARGET]["flops"]
        assert a["t_compute_s"] == pytest.approx(1e9 / peak)
        assert a["bottleneck"] in ("compute", "memory", "collective")
        assert 0 < a["compute_fraction"] <= 1.0

    def test_fits_flag(self):
        a = roofline.analyze(self._rec())
        assert a["fits_hbm_16g"] is True
        big = self._rec(memory={"temp_size_in_bytes": int(2e10),
                                "argument_size_in_bytes": 0})
        assert roofline.analyze(big)["fits_hbm_16g"] is False

    def test_skipped_cells_none(self):
        assert roofline.analyze({"status": "skipped"}) is None

    def test_no_probe_falls_back(self):
        rec = self._rec()
        rec.pop("probe")
        a = roofline.analyze(rec)
        assert a["flops_per_device"] == pytest.approx(1e9)


class TestAchievedVsPeak:
    COST = {"flops": 2e9, "bytes": 4e9}

    def test_known_device_priced_at_its_own_peaks(self):
        out = roofline.achieved_vs_peak(1e-3, self.COST, "TPU v5 lite")
        peak = roofline.PEAKS["TPU v5 lite"]
        assert out["achieved_gflops"] == pytest.approx(2e3)
        assert out["frac_peak_flops"] == pytest.approx(2e12 / peak["flops"])
        assert out["frac_peak_bw"] == pytest.approx(4e12 / peak["hbm_bw"])

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100"])
    def test_unknown_device_gets_no_peak_share(self, kind):
        out = roofline.achieved_vs_peak(1e-3, self.COST, kind)
        assert out["achieved_gbps"] == pytest.approx(4e3)
        assert not any(k.startswith("frac_peak") for k in out)


class TestShippedArtifacts:
    """The shipped dry-run results must stay complete and error-free."""

    PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.jsonl")

    @pytest.mark.skipif(not os.path.exists(PATH), reason="no sweep artifact")
    def test_all_cells_ok_or_noted_skip(self):
        rows = [json.loads(l) for l in open(self.PATH)]
        keys = {(r["arch"], r["shape"], r["mesh"]) for r in rows}
        assert len(keys) == 86          # 40 assigned ×2 meshes + 3 LP ×2
        assert all(r["status"] in ("ok", "skipped") for r in rows)
        skips = [r for r in rows if r["status"] == "skipped"]
        assert len(skips) == 8
        assert all(r["shape"] == "long_500k" for r in skips)

    @pytest.mark.skipif(not os.path.exists(PATH), reason="no sweep artifact")
    def test_probes_present_for_scanned_cells(self):
        rows = [json.loads(l) for l in open(self.PATH)]
        for r in rows:
            if r["status"] == "ok" and r.get("meta", {}).get("scan_trip"):
                assert "probe" in r, (r["arch"], r["shape"], r["mesh"])
