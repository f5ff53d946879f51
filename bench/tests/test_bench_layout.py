"""The harness finds every part of a cell by name, its generators are
deterministic in the seed, its arithmetic matches the program's parameter
counts, and it refuses to run without a TPU."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import flops, gen, peaks  # noqa: E402
from bench import run as run_mod  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    loaded = run_mod.load_cell(BENCHMARK, cell)
    assert loaded["kind"].KIND == loaded["traffic"]["kind"]
    assert loaded["config"]["name"] == loaded["cell"]["config"]
    for m in run_mod.cell_metrics(BENCHMARK, loaded["cell"], True):
        assert (run_mod.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert run_mod.cell_metrics(BENCHMARK, loaded["cell"], False)


def test_a_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(run_mod.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    traffic = json.loads((bench / "traffic" / "train.all.json").read_text())
    traffic["seq_len"] = 4096
    (bench / "traffic" / "train.long.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "steps.train.py").write_text(
        "def read(r):\n    return r['window']['tokens'] / 4096\n")
    b = json.loads(json.dumps(BENCHMARK))
    b["workloads"].append({"name": "xlstm_l12_d768.train.long",
                           "config": "xlstm_l12_d768", "traffic": "train.long",
                           "chips": 1, "why": "longer sequences"})
    b["per_layer"].append({"name": "steps.train", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "model step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["xlstm_l12_d768.train.long"]})
    loaded = run_mod.load_cell(b, "xlstm_l12_d768.train.long", bench)
    assert loaded["traffic"]["seq_len"] == 4096
    wanted = run_mod.cell_metrics(b, loaded["cell"], True)
    assert [m["name"] for m in wanted] == ["steps.train"]
    got = run_mod.read_metrics(wanted, {"window": {"tokens": 8192}}, bench)
    assert got == {"steps.train": {"value": 2.0, "unit": "steps"}}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run_mod.load_cell(BENCHMARK, "no.such.cell")


def test_a_per_layer_metric_must_list_its_cells():
    b = json.loads(json.dumps(BENCHMARK))
    del b["per_layer"][0]["workloads"]
    with pytest.raises(KeyError, match="lists no workloads"):
        run_mod.cell_metrics(b, b["workloads"][0], True)
    assert run_mod.cell_metrics(b, b["workloads"][0], False)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    wanted = [m for m in BENCHMARK["per_layer"]
              if m["name"] == "probe_kernel_frac.serve"]
    assert run_mod.read_metrics(wanted, {"kind": "serve",
                                         "trace": None}) == {}


def test_lm_batches_are_deterministic_in_the_seed_and_match_the_program():
    from repro.data import DataConfig
    from repro.data.pipeline import SyntheticLM

    kw = dict(vocab=1000, seq_len=64, batch=4)
    a = gen.lm_batch(2**31 + 7, 3, **kw)
    b = gen.lm_batch(2**31 + 7, 3, **kw)
    c = gen.lm_batch(2**31 + 8, 3, **kw)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    prog = SyntheticLM(DataConfig(vocab=1000, seq_len=64, global_batch=4,
                                  seed=2**31 + 7)).batch_at(3)
    assert np.array_equal(a["tokens"], prog["tokens"])
    assert np.array_equal(a["targets"], prog["targets"])


def test_serve_requests_keep_their_sizes_across_seeds():
    tr = json.loads((run_mod.BENCH / "traffic" / "serve.all.json")
                    .read_text())
    a = gen.serve_requests(tr, 40, 151936, 2**31 + 3)
    b = gen.serve_requests(tr, 40, 151936, 2**31 + 3)
    c = gen.serve_requests(tr, 40, 151936, 12)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    sizes = lambda reqs: sorted((p.shape[1], o) for p, o in reqs)  # noqa
    assert sorted(p.shape[1] for p, _ in a) == sorted(p.shape[1]
                                                      for p, _ in c)
    assert [o for _, o in a] == [o for _, o in c]
    assert sizes(a) != sizes(c) or not np.array_equal(a[0][0], c[0][0])
    outs = [o for _, o in a]
    assert outs == sorted(outs, reverse=True)
    assert min(p.shape[1] for p, _ in a) >= tr["prompt"]["min"]
    assert max(p.shape[1] for p, _ in a) <= tr["prompt"]["max"]
    assert all(p.min() >= 1 for p, _ in a)


def test_lognormal_sizes_are_quantiles():
    d = {"median": 100, "sigma": 1.0, "min": 1, "max": 10**6}
    s = gen.lognormal_sizes(101, d)
    assert s == sorted(s) and s[50] == 100
    assert gen.pow2_bucket(100, 8) == 128 and gen.pow2_bucket(4, 8) == 8


@pytest.mark.parametrize("config,program_id",
                         [("xlstm_l12_d768", "xlstm_125m"),
                          ("qwen3_14b_l8", "qwen3_14b")])
def test_model_flops_count_the_programs_parameters(config, program_id):
    import jax

    from repro.configs import model_config
    from repro.models.params import is_spec
    from repro.models.registry import Arch

    cfg = json.loads((run_mod.BENCH / "configs" / f"{config}.json")
                     .read_text())
    ref = run_mod.load_module(run_mod.BENCH / "configs" / f"{config}.py")
    prog = model_config(program_id).replace(
        n_layers=cfg["model"]["n_layers"])
    specs = jax.tree_util.tree_leaves(Arch(prog).param_specs(),
                                      is_leaf=is_spec)
    total = sum(int(np.prod(s.shape)) for s in specs)
    n = flops.matmul_params(ref, cfg["model"])
    assert n == total - prog.vocab * prog.d_model
    assert flops.train_flops_per_token(ref, cfg["model"]) == 6.0 * n
    assert flops.serve_flops_per_token(ref, cfg["model"]) == 2.0 * n


def test_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_the_command_refuses_a_machine_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(ROOT / ".bench_work")})
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
