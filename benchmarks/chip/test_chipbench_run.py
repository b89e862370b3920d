"""CPU tests of whole runs at a tiny size: a cell made of new files only,
both loops, the traced path, faults that must read as not correct, and the
command's refusal off a TPU.

The tiny cells live in a temporary repository root: its ``BENCHMARK.json``
lists ``paths: ["extra", "chip"]``, where ``chip`` links to this directory
and ``extra`` holds the new configuration, mixes, limits and a new metric.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
SEED = 2 ** 31 + 11
# stand-in peaks for the CPU, so that the readers that need peaks run
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# The tiny cells' gap limit.  Their program computes in float32, so sound
# runs read a gap of 0 on every seed tried; the fp8 control read 0.042 to
# 0.081 over four seeds of each cell (CPU).
TINY_LIMIT = 0.01


def _tiny_root(tmp: pathlib.Path, gap_limit: float) -> pathlib.Path:
    extra = tmp / "extra"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (extra / sub).mkdir(parents=True)
    os.symlink(HERE, tmp / "chip")
    conf = json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())
    conf.update(name="tiny", hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, vocab_size=256,
                torch_dtype="float32")
    conf["serving"] = dict(conf["serving"], commit_section_tokens=32)
    (extra / "configs" / "tiny.json").write_text(json.dumps(conf))
    # short documents and long outputs, so that decoded tokens are a large
    # share of what a decode step attends to
    docs = {"count": 2, "tokens": 32}
    mixes = {
        "tiny-open": {"loop": "open", "rate_per_s": 20, "block": 4,
                      "documents": docs,
                      "suffix_tokens": {"values": [16, 32]},
                      "output_tokens": {"values": [0]}},
        "tiny-closed": {"loop": "closed", "batch": 4, "block": 4,
                        "documents": docs,
                        "suffix_tokens": {"values": [16, 32]},
                        "output_tokens": {"values": [16, 24, 32, 40]},
                        "engine": {"num_slots": 2, "max_seq": 192}},
    }
    for name, mix in mixes.items():
        (extra / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for cell in ("tiny.open", "tiny.closed"):
        (extra / "limits" / f"{cell}.json").write_text(json.dumps(
            {"max_logit_gap": gap_limit, "sample_served_tokens": 40}))
    (extra / "metrics" / "requests_served.py").write_text(
        "def read(run):\n"
        "    return sum(r['served'] is not None for r in run.requests)\n")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["paths"] = ["extra", "chip"]
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "extra/configs/tiny.json", "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"}]
    bench["end_to_end"] = [
        {"name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny.open"]},
        {"name": "output_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny.closed"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    bench["per_layer"] = [
        {"name": "requests_served", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "admission", "moves": "ttft_p50_ms"},
        {"name": "layer_step_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "per-layer compute",
         "moves": "ttft_p50_ms", "workloads": ["tiny.open"]},
        {"name": "decode_mfu", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "model step",
         "moves": "output_tokens_per_s", "workloads": ["tiny.closed"]}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def jax_config():
    """Runs turn the persistent compilation cache on; put it back."""
    import jax
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(root, workload, trace=False):
    from chipbench import harness
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.peaks_mod, "peaks_for", lambda kind: CPU_PEAKS)
        return harness.run(root, workload, SEED, 1.0, trace,
                           t_start=time.perf_counter(), check_device=False)


def test_cell_of_new_files_is_found_and_loaded(tmp_path):
    from chipbench.layout import Layout
    root = _tiny_root(tmp_path, TINY_LIMIT)
    layout = Layout(root)
    cell = layout.cell("tiny.open")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["loop"] == "open"
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["requests_served",
                                                   "layer_step_ms"]
    assert layout.metric("requests_served").read is not None
    assert layout.metric("ttft_p70_ms").read is not None   # found in chip/
    with pytest.raises(KeyError):
        layout.cell("tiny.absent")


@pytest.mark.parametrize("workload,trace", [
    ("tiny.open", False), ("tiny.open", True),
    ("tiny.closed", False), ("tiny.closed", True)])
def test_whole_run_at_tiny_size(tmp_path, jax_config, workload, trace):
    out = _run(_tiny_root(tmp_path, TINY_LIMIT), workload, trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = set(out["metrics"])
    if trace:
        # requests_served has no workloads key: it is read wherever its
        # end-to-end metric, ttft_p50_ms, is
        if workload == "tiny.open":
            assert names == {"requests_served", "layer_step_ms"}
            assert out["metrics"]["requests_served"]["value"] == \
                out["attempted"]
        else:
            assert names == {"decode_mfu"}
        assert out["device"]["window_s"] > 0
    else:
        assert names == {"setup_s", "ttft_p50_ms" if workload == "tiny.open"
                         else "output_tokens_per_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["served_tokens_compared"]["value"] >= 40 or \
        workload == "tiny.open"


@pytest.mark.parametrize("workload,fault", [
    ("tiny.open", "altered_token"), ("tiny.closed", "altered_token"),
    ("tiny.closed", "state_unchanged")])
def test_faults_read_not_correct(tmp_path, jax_config, monkeypatch,
                                 workload, fault):
    from chipbench.faults import FAULTS
    FAULTS[fault](monkeypatch.setattr)
    out = _run(_tiny_root(tmp_path, TINY_LIMIT), workload)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


@pytest.mark.parametrize("workload", ["tiny.open", "tiny.closed"])
def test_control_reads_not_correct(tmp_path, jax_config, workload):
    """The reference in fp8, in the program's place, on three seeds: through
    the run's own check, at the cell's limits and sample, the program reads
    correct and the control does not."""
    from chipbench import harness
    from chipbench.layout import Layout

    layout = Layout(_tiny_root(tmp_path, TINY_LIMIT))
    cell = layout.cell(workload)
    limits = json.loads(layout.find("limits", workload + ".json").read_text())
    for seed in (SEED, 5, 2 ** 31 + 3):
        traffic, system = harness.prepare(cell, seed)
        records, _, _ = harness.drive(system, traffic, 2.0)
        failed, program, control = harness.check(
            system.params, cell.config, traffic, records, limits, seed,
            control=True)
        assert failed == 0 and harness.compared_ok(program), (seed, program)
        assert not harness.compared_ok(control), (seed, control)
        assert control["max_logit_gap"]["value"] > TINY_LIMIT


def _command(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-0.6b.doc-qa-4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_a_device_that_is_not_a_tpu():
    p = _command(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_command_refuses_without_the_program(tmp_path):
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
