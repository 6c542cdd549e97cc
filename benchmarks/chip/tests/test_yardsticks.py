"""The benchmark's fixed arithmetic and its manifest."""
import json
import re
from pathlib import Path

import pytest

import flops
import xplane

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
STABLELM = json.loads((CHIP / "configs" / "stablelm-1.6b.json").read_text())


def test_stablelm_flops_match_a_hand_count():
    # per layer and token: q, k, v, o are 2048 x 2048 (4 * 2 * 2048 * 2048),
    # gate, up, down are 2048 x 5632 (3 * 2 * 2048 * 5632), and q.k plus p.v
    # over c positions of 32 x 64 (2 * 2 * c * 2048); 24 layers; the output
    # head is 2048 x 100352
    layer = 4 * 2 * 2048 * 2048 + 3 * 2 * 2048 * 5632
    assert layer == 102_760_448
    head = 2 * 2048 * 100_352
    assert flops.dense_decoder_token_flops(STABLELM, 1, logits=False) == 24 * (layer + 4 * 2048)
    assert flops.dense_decoder_token_flops(STABLELM, 600, logits=True) == \
        24 * (layer + 4 * 600 * 2048) + head
    # 8 prompts of 512, 64 decode steps: prefill positions attend to 1..512,
    # decode steps to 513..576; logits at the prompt's last position and at
    # every decode step
    prefill = 512 * 24 * layer + 24 * 4 * 2048 * (512 * 513 // 2) + head
    decode = 64 * (24 * layer + head) + 24 * 4 * 2048 * sum(range(513, 577))
    assert prefill == 1_288_951_562_240
    assert decode == 190_998_118_400
    assert flops.serve_session_flops(STABLELM, 8, 512, 64) == 8 * (prefill + decode)


@pytest.mark.parametrize("nbytes", [4, 8, 64, 65, 8 * 2**20 - 5, 453_771_264])
def test_delta_bytes_follow_the_object_not_the_tiles(nbytes):
    # both objects read once, one int32 flag per 64-byte block written,
    # whatever tile rows, lane widths or padding the kernel uses
    blocks = -(-nbytes // 64)
    assert flops.delta_mask_bytes(nbytes) == 2 * nbytes + 4 * blocks


def test_delta_bytes_do_not_move_with_the_kernels_tile_size(monkeypatch):
    kernel = pytest.importorskip("repro.kernels.delta_snapshot.kernel")
    before = [flops.delta_mask_bytes(n) for n in (65, 131_073 * 64)]
    monkeypatch.setattr(kernel, "TILE_WORDS", 1 << 12)
    assert [flops.delta_mask_bytes(n) for n in (65, 131_073 * 64)] == before


def test_interval_union_and_clip():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert xplane.union_length(iv) == 15 + 11
    assert xplane.merged(iv) == [(0, 15), (20, 31)]
    assert xplane.clip(iv, 8, 25) == [(8, 10), (8, 15), (20, 25)]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_manifest_names_units_and_files():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/")
        assert ".." not in p.split("/")
    assert len(m["command"]) <= 32 and all(LINE.match(w) for w in m["command"])
    confs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]) and LINE.match(c["why"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    names = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in confs
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        names.add(w["name"])
    assert len(names) == len(m["workloads"])
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(names)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher") and 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    layers = {}
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"]) and LINE.match(p["layer"])
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert p["moves"] in e2e and p["better"] in ("lower", "higher")
        assert all(c in names for c in p.get("workloads", names))
        assert (CHIP / "metrics" / f"{p['name']}.py").is_file()
        assert p["name"] not in layers and p["name"] not in e2e
        layers[p["name"]] = p
    for w in m["workloads"]:
        reported = [e for e in m["end_to_end"] if w["name"] in e.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in p.get("workloads", [w["name"]]) for p in m["per_layer"])
    assert len(json.dumps(m)) <= 64 * 1024


def test_peaks_name_their_source():
    peaks = json.loads((CHIP / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
