"""FLOP and byte counts against hand counts, the chip's memory peak, and
the harness finding a cell's files by name, a new model family's among
them."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chipbench_cells

from benchmarks.chip import flops, harness, peaks

CONF = harness.HERE / "configs"
DECODER = harness.load_module(harness.HERE / "families" / "decoder.py")
FAMILY_GIVES = ("SCOPES", "model_config", "weights", "to_program",
                "from_program", "loss_and_grad", "train_flops_per_token",
                "dims")


def conf(name):
    return harness.load_json(CONF / f"{name}.json")


# ------------------------------------------------------------------ counts

def test_opt_125m_counts_by_hand():
    c = conf("opt-125m")
    # per layer: q, k, v, o of 768 x 768, MLP 768 x 3072 twice
    layer = 4 * 768 * 768 + 2 * 768 * 3072
    assert DECODER.matmul_params(c) == 12 * layer + 768 * 50272 \
        == 123_543_552
    assert DECODER.attn_pair_flops(c) == 4 * 12 * 64 * 12
    # 6 N plus causal attention, 2049/2 keys per query on average
    assert DECODER.train_flops_per_token(c, 2048) == \
        3 * (2 * 123_543_552 + 36_864 * 2049 / 2) == 854_562_816


def test_attention_cost_by_hand():
    B, S, H, K, hd = 2, 4, 3, 1, 8
    fl, by = flops.attention_cost(B, S, H, K, hd)
    assert fl == 4 * B * H * hd * (4 + 3 + 2 + 1)
    q, kv, lse = B * S * H * hd * 2, B * S * K * hd * 2, B * H * S * 4
    assert by == q + 2 * kv + q + lse
    fl_b, by_b = flops.attention_cost(B, S, H, K, hd, backward=True)
    assert fl_b == 2 * fl
    assert by_b == (q + 2 * kv + 2 * q + lse) + (q + 2 * kv)
    pk = peaks.lookup("TPU v5 lite")
    assert flops.roofline_s(fl, by, pk) == max(fl / 197e12, by / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


# ------------------------------------------------------------------ memory

class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_counts_the_reserved_scratch():
    """The program's scratch is reserved apart from the buffers in use;
    the peak is their sum on the fullest chip."""
    chips = [_Device({"peak_bytes_in_use": 1_830_061_056,
                      "peak_bytes_reserved": 13_654_065_152}),
             _Device({"peak_bytes_in_use": 2_000_000_000,
                      "peak_bytes_reserved": 0})]
    assert harness.memory_peak_bytes(chips) == 15_484_126_208
    assert harness.memory_peak_bytes([_Device(None)]) == 0


# ---------------------------------------------------- finding files by name

# a new family as a file: the decoder under another name, whose program
# names one scope more
FAMILY = """from benchmarks.chip.families.decoder import *  # noqa: F401,F403
from benchmarks.chip.families.decoder import SCOPES as _DECODER

SCOPES = _DECODER + ("router",)
"""


def _mini_benchmark(tmp_path):
    """A benchmark directory holding new cells, configurations, a family,
    mixes and a metric, made of files alone.  ``m-2.tiny-train`` is the
    training cell at the size of ``chipbench_cells.tiny_train_cell``,
    its configuration of the new family."""
    here = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "families", "traffic", "metrics", "runners",
              "limits"):
        (here / d).mkdir(parents=True)
    (here / "families" / "decoder-x.py").write_text(FAMILY)
    (here / "configs" / "m-1.json").write_text(
        json.dumps({"name": "m-1", "family": "decoder-x"}))
    tiny = chipbench_cells.tiny_train_cell()
    (here / "configs" / "m-2.json").write_text(
        json.dumps(dict(tiny.config, name="m-2", family="decoder-x")))
    (here / "traffic" / "mix-a.json").write_text(
        json.dumps({"runner": "echo", "rate": 3}))
    (here / "traffic" / "tiny-train.json").write_text(
        json.dumps(tiny.traffic))
    (here / "runners" / "echo.py").write_text(
        "def run(cell, *a, **k):\n    return cell.traffic['rate']\n\n\n"
        "def calibrate(cell, seeds, control, faults, devices):\n"
        "    for s in seeds:\n"
        "        yield {'seed': s, 'kind': 'program', 'gap': 0.0}\n")
    (here / "metrics" / "hits.serve.py").write_text(
        "LAYER = 'cache'\nMOVES = 'x_ms'\n\n\ndef read(obs):\n"
        "    return obs.get('hits')\n")
    bench = {
        "configs": [{"name": "m-1", "file": "benchmarks/chip/configs/m-1.json"},
                    {"name": "m-2", "file": "benchmarks/chip/configs/m-2.json"}],
        "workloads": [{"name": "m-1.mix-a", "config": "m-1",
                       "traffic": "mix-a", "chips": 1},
                      {"name": "m-2.tiny-train", "config": "m-2",
                       "traffic": "tiny-train", "chips": 1}],
        "end_to_end": [{"name": "x_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"},
                       {"name": "y", "unit": "s", "workloads": ["other"]}],
        "per_layer": [{"name": "hits.serve", "unit": "%", "moves": "x_ms"}]}
    return bench, here


def test_cell_found_from_files_alone(tmp_path):
    bench, here = _mini_benchmark(tmp_path)
    cell = harness.find_cell(bench, "m-1.mix-a", root=tmp_path, here=here)
    assert cell.config == {"name": "m-1", "family": "decoder-x"}
    assert cell.family.SCOPES == DECODER.SCOPES + ("router",)
    assert all(hasattr(cell.family, k) for k in FAMILY_GIVES)
    assert cell.traffic["rate"] == 3
    assert [m["name"] for m in cell.end_to_end] == ["x_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["hits.serve"]
    echo = harness.runner(cell, here=here)
    assert echo.run(cell) == 3
    assert [r["seed"] for r in echo.calibrate(cell, [5, 6], [], [],
                                             [])] == [5, 6]
    reader = harness.metric_reader("hits.serve", here=here)
    assert reader.read({"hits": 42.0}) == 42.0
    assert reader.read({}) is None
    with pytest.raises(KeyError):
        harness.find_cell(bench, "no.such.cell", root=tmp_path, here=here)
    # the benchmark's own training runner drives the new family's cell
    cell = harness.find_cell(bench, "m-2.tiny-train", root=tmp_path,
                             here=here)
    limits = harness.load_json(harness.HERE / "limits"
                               / "train.opt-125m.s2048.json")
    out = harness.runner(cell).run(cell, 2 ** 33 + 7, 0.3, False, tmp_path,
                                   jax.devices("cpu"), limits)
    assert out.correct, out.checks
    assert out.obs["family"] is cell.family


def test_every_cell_of_the_benchmark_resolves():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        run = harness.runner(cell)
        assert callable(run.run) and callable(run.calibrate)
        assert (harness.HERE / "limits" / f"{cell.name}.json").exists()
        assert all(hasattr(cell.family, k) for k in FAMILY_GIVES)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            reader = harness.metric_reader(m["name"])
            assert reader.MOVES == m["moves"] and reader.LAYER == m["layer"]
            assert m["moves"] in names


def test_importing_the_benchmark_describes_no_topology():
    """Every module imports without loading the TPU library or
    initialising a JAX backend."""
    code = (
        "import sys, pkgutil, importlib\n"
        f"sys.path.insert(0, {str(harness.ROOT)!r})\n"
        "import benchmarks.chip as c\n"
        "from benchmarks.chip import harness\n"
        "for p in sorted(harness.HERE.rglob('*.py')):\n"
        "    if 'tests' in p.parts: continue\n"
        "    if p.parent == harness.HERE and p.stem != '__init__':\n"
        "        importlib.import_module('benchmarks.chip.' + p.stem)\n"
        "    elif p.parent.name in ('metrics', 'runners', 'references',\n"
        "                           'families'):\n"
        "        harness.load_module(p)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")
