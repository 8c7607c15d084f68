"""The benchmark's yardstick: operations per token, the peaks table,
and the harness finding every configuration, mix and metric by name."""

from __future__ import annotations

import json
import re

import pytest

from benchmarks.chip import flops, peaks, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_nanogpt_paper_training_flops_match_the_hand_count():
    # 6 x 10.6M block weights + 6 x 384 x 50304 head + 12 x 6 x 384 x 512
    hand = 6 * 6 * (4 * 384 ** 2 + 2 * 384 * 1536) + 6 * 384 * 50304 \
        + 12 * 6 * 384 * 512
    got = flops.decoder_train_flops_per_token(6, 384, 1536, 50304, 512)
    assert got == hand
    assert got == pytest.approx(194e6, rel=2e-3)


def test_forward_is_a_third_of_training():
    f = flops.decoder_forward_flops_per_token(2, 64, 128, 100, 16)
    assert flops.decoder_train_flops_per_token(2, 64, 128, 100, 16) == 3 * f


def test_peaks_of_the_v5e_and_an_unknown_kind_refused():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_every_cell_finds_its_configuration_mix_and_reference(bench):
    for cell in bench["workloads"]:
        cfg = registry.config(cell["config"])
        assert cfg["name"] == cell["config"]
        mix = registry.traffic(cell["traffic"])
        assert mix["kind"] in ("sweep", "train")
        ref = registry.reference(cell["config"])
        assert callable(getattr(ref, "simulate", None)) \
            or callable(getattr(ref, "train_steps", None))


def test_every_per_layer_metric_has_a_reader_that_finds_nothing_in_nothing(
        bench):
    for m in bench["per_layer"]:
        reader = registry.metric_reader(m["name"])
        assert reader.read({}) is None


def test_a_cell_reports_setup_an_end_to_end_metric_and_a_layer(bench):
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in registry.end_to_end(bench, cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.per_layer(bench, cell)
        assert layer and all(m["moves"] in e2e for m in layer)


def test_a_missing_piece_is_an_error_not_a_default(bench):
    with pytest.raises(KeyError):
        registry.workload(bench, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        registry.config("no-such-config")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no.such.metric")
    with pytest.raises(ValueError):
        registry.traffic("../../BENCHMARK")


def test_a_new_piece_is_found_by_its_file_alone(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps({"kind": "sweep"}))
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(obs):\n    return obs.get('x')\n")
    monkeypatch.setattr(registry, "HERE", tmp_path)
    assert registry.traffic("new_mix") == {"kind": "sweep"}
    assert registry.metric_reader("new.metric").read({"x": 2.5}) == 2.5


def test_names_units_and_keys_keep_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/chip/")
    cells = bench["workloads"]
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
