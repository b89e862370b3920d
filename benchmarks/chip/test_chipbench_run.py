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
TINY_MEAN_LIMIT = 0.001


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
    limits = harness.read_limits(layout, workload)
    for seed in (SEED, 5, 2 ** 31 + 3):
        traffic, system = harness.prepare(cell, seed)
        records, _, _ = harness.drive(system, traffic, 2.0)
        failed, program, control = harness.check(
            system.params, cell, traffic, records, limits, seed,
            control=True)
        assert failed == 0 and harness.compared_ok(program), (seed, program)
        assert not harness.compared_ok(control), (seed, control)
        assert control["max_logit_gap"]["value"] > TINY_LIMIT


def _mean_gap_root(tmp_path) -> pathlib.Path:
    """The tiny root, with tiny.open's limits bounding the mean gap in
    place of the widest."""
    root = _tiny_root(tmp_path, TINY_LIMIT)
    (root / "extra" / "limits" / "tiny.open.json").write_text(json.dumps(
        {"mean_logit_gap": TINY_MEAN_LIMIT, "sample_served_tokens": 40}))
    return root


def test_altered_token_fails_a_mean_gap_limit(tmp_path, jax_config,
                                              monkeypatch):
    from chipbench.faults import FAULTS
    FAULTS["altered_token"](monkeypatch.setattr)
    out = _run(_mean_gap_root(tmp_path), "tiny.open")
    assert out["correct"] is False
    assert set(out["checks"]) == {"mean_logit_gap", "served_tokens_compared"}
    assert out["checks"]["mean_logit_gap"]["value"] > 10 * TINY_MEAN_LIMIT


def test_control_fails_a_mean_gap_limit(tmp_path, jax_config):
    """A limits file may bound the mean gap in place of the widest: then
    only the mean is compared, the program reads correct and the control
    does not, on three seeds."""
    from chipbench import harness
    from chipbench.layout import Layout

    layout = Layout(_mean_gap_root(tmp_path))
    cell = layout.cell("tiny.open")
    limits = harness.read_limits(layout, "tiny.open")
    for seed in (SEED, 5, 2 ** 31 + 3):
        traffic, system = harness.prepare(cell, seed)
        records, _, _ = harness.drive(system, traffic, 2.0)
        failed, program, control = harness.check(
            system.params, cell, traffic, records, limits, seed,
            control=True)
        assert set(program) == {"mean_logit_gap", "served_tokens_compared"}
        assert failed == 0 and harness.compared_ok(program), (seed, program)
        assert not harness.compared_ok(control), (seed, control)


def _retype(root: pathlib.Path, model_type: str) -> dict:
    """The tiny configuration, given ``model_type``; returns it."""
    path = root / "extra" / "configs" / "tiny.json"
    conf = dict(json.loads(path.read_text()), model_type=model_type)
    path.write_text(json.dumps(conf))
    return conf


def test_architecture_of_new_files_runs_whole(tmp_path, jax_config):
    """A model_type described only by a module under ``extra/archs/`` (the
    Qwen3 equations under a new name) runs a whole traced run: its
    program config builds the system, its reference decides ``correct``,
    and its FLOP counts give ``decode_mfu``."""
    from chipbench.layout import Layout

    root = _tiny_root(tmp_path, TINY_LIMIT)
    module = root / "extra" / "archs" / "tiny_decoder.py"
    module.parent.mkdir()
    module.write_text((HERE / "archs" / "qwen3.py").read_text())
    _retype(root, "tiny_decoder")
    assert not (HERE / "archs" / "tiny_decoder.py").exists()
    assert Layout(root).cell("tiny.closed").arch.__file__ == str(module)
    out = _run(root, "tiny.closed", trace=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["decode_mfu"]["value"] > 0


def test_unknown_model_type_fails_at_setup_naming_the_file(tmp_path):
    root = _tiny_root(tmp_path, TINY_LIMIT)
    _retype(root, "absent_arch")
    with pytest.raises(FileNotFoundError) as e:
        _run(root, "tiny.open")
    for d in ("extra", "chip"):
        assert str(root / d / "archs" / "absent_arch.py") in str(e.value)


@pytest.mark.parametrize("limits", [
    {"sample_served_tokens": 40},
    {"max_logit_gap_": 0.01, "sample_served_tokens": 40},
    {"max_logit_gap": 0.01, "mean_gap": 0.001, "sample_served_tokens": 40},
    {"max_logit_gap": 0.01}], ids=["no-bound", "misspelt", "unknown-key",
                                   "no-sample"])
def test_limits_that_bound_no_gap_fail_at_setup(tmp_path, monkeypatch,
                                                limits):
    """A limits file that bounds no gap statistic, or names a key that
    nothing reads, would let any logits read correct: the run refuses it
    before it builds anything."""
    from chipbench import harness

    root = _tiny_root(tmp_path, TINY_LIMIT)
    path = root / "extra" / "limits" / "tiny.open.json"
    path.write_text(json.dumps(limits))

    def never(*args, **kwargs):
        raise AssertionError("set-up went on past a bad limits file")
    monkeypatch.setattr(harness, "prepare", never)
    with pytest.raises(ValueError) as e:
        _run(root, "tiny.open")
    assert str(path) in str(e.value)


def test_blocked_attention_equals_one_block(tmp_path, monkeypatch):
    """The reference with its attention cut into blocks of query rows (the
    score budget patched small) reads what it reads in one block: the
    committed document's keys and values, and the logits of a suffix."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import arch, reference
    from chipbench.weights import make_params
    from repro.models import build_model

    conf = json.loads((_tiny_root(tmp_path, TINY_LIMIT) / "extra" / "configs"
                       / "tiny.json").read_text())
    source = HERE / "archs" / "qwen3.py"
    one = arch.load_module(source, "qwen3_one_block")
    params = make_params(build_model(one.program_config(conf)), SEED)
    rng = np.random.default_rng(0)
    doc = rng.integers(0, 256, 64, dtype=np.int32)   # two sections of 32
    suffix = rng.integers(0, 256, 40, dtype=np.int32)

    def read(mod):
        ref = mod.Reference(params, conf)
        kv = ref.prefix_kv(doc)
        return kv, ref.logits(kv, len(doc), suffix, 0, len(suffix))

    kv1, lg1 = read(one)
    budget = 1000
    monkeypatch.setattr(reference, "SCORE_BLOCK", budget)
    # 2 key heads x 2 query heads each x 32 rows x 32 keys: 5 blocks
    q = jnp.zeros((32, 2, 2, 16))
    k = jnp.zeros((32, 2, 16))
    jaxpr = jax.make_jaxpr(reference.causal_attention)(q, k, k)
    assert sum(e.primitive.name == "dot_general"
               for e in jaxpr.eqns) == 2 * 5
    kv2, lg2 = read(arch.load_module(source, "qwen3_blocked"))
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg1), rtol=0,
                               atol=1e-6)
    for (k1, v1), (k2, v2) in zip(kv1, kv2):
        np.testing.assert_allclose(np.asarray(k2), np.asarray(k1), rtol=0,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(v2), np.asarray(v1), rtol=0,
                                   atol=1e-6)


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
