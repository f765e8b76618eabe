"""A whole benchmark run at a tiny size on the CPU, past the harness's look
for a chip: a sound run comes out correct; the lower-precision control, put
in the program's place, does not; nor do runs with the timed path broken
underneath.  Also the shape of ``BENCHMARK.json`` against its files."""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import check, run  # noqa: E402

SEED = 2_147_483_659  # past 2**31, as the benchmark's seeds are

GALAXY = {"name": "tiny-galaxy", "family": "galaxy_postln",
          "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
          "intermediate_size": 256, "vocab_size": 512, "layer_norm_eps": 1e-5,
          "dtype": "float32", "matmul_precision": "default", "chips": 1,
          "plan": "even", "check": {"logit_err": 0.01,
                                    "control": "float8_e4m3fn"}}
SINGLE = {"kind": "single", "clients": 4, "prompt_tokens": [8, 16],
          "output_tokens": [2, 8],
          "engine": {"max_batch": 4, "max_len": 32, "page_size": 16,
                     "prefix_cache": False}}


class Fault:
    """The executor with its decode step broken underneath the tap."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class StaleState(Fault):
    """The step writes its K/V into a copy and returns the pool unchanged."""

    def decode_paged(self, tokens, pool, block_table, positions):
        import jax
        import jax.numpy as jnp
        logits, _ = self.inner.decode_paged(
            tokens, jax.tree.map(jnp.copy, pool), block_table, positions)
        return logits, pool


class HalfBatch(Fault):
    """Only the first half of the slots is computed; the other half's rows
    repeat it."""

    def decode_paged(self, tokens, pool, block_table, positions):
        import jax.numpy as jnp
        logits, pool = self.inner.decode_paged(tokens, pool, block_table,
                                               positions)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), pool


@pytest.fixture(autouse=True)
def keep_every_request(monkeypatch):
    """A tiny run sends few requests: keep the rows of all of them."""
    monkeypatch.setattr(check, "KEEP_SHARE", 1.0)


def _run(conf=GALAXY, mix=SINGLE, **kw):
    import jax
    spec = run.benchmark()
    return run.run_cell(conf, mix, seed=SEED, seconds=0.25, trace=False,
                        devices=jax.devices(), peaks=None,
                        e2e=spec["end_to_end"], per_layer=[], **kw)


@pytest.mark.parametrize("conf", [GALAXY], ids=["galaxy"])
def test_sound_run_is_correct_and_altered_tokens_are_not(conf, monkeypatch):
    res = _run(conf)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   run.benchmark()["end_to_end"]}
    assert list(res)[-1] == "checks"
    assert res["checks"]["unmatched"]["value"] == 0
    # a token altered where it is sampled
    from repro.serving import ServingEngine

    sample = ServingEngine._sample
    monkeypatch.setattr(ServingEngine, "_sample", lambda self, logits: (
        sample(self, logits) + 1) % logits.shape[-1])
    bad = _run(conf)
    assert not bad["correct"]
    assert bad["checks"]["off_argmax"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct():
    """float8 operands: the control's logits err past the limit, while the
    program's readings of the same run stay within it."""
    res = _run(control=True)
    assert not res["correct"]
    limit = GALAXY["check"]["logit_err"]
    assert res["checks"]["logit_err"]["value"] > 3 * limit
    assert res["checks"]["program_logit_err"]["value"] < limit / 3


@pytest.mark.parametrize("fault", [StaleState, HalfBatch])
def test_a_broken_step_is_not_correct(fault):
    res = _run(wrap=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_err"]["value"] > GALAXY["check"]["logit_err"]


def test_no_result_without_a_chip_or_without_the_program(tmp_path):
    """On the CPU the command exits nonzero and prints no result line; so it
    does from a directory holding only the benchmark's own files."""
    import shutil

    spec = run.benchmark()
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, p), alone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = spec["command"] + ["--workload", spec["workloads"][0]["name"],
                             "--seed", str(SEED), "--seconds", "1",
                             "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(cmd, cwd=where, env=env, text=True,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE)
             for where in (ROOT, alone)]
    for proc in procs:
        out, _ = proc.communicate(timeout=300)
        assert proc.returncode != 0
        assert '"correct"' not in out


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    spec = run.benchmark()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        conf = run.load_json(os.path.join(ROOT, c["file"]))
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) == set(conf["reduced"])
        assert os.path.exists(os.path.join(ROOT, "perfbench", "families",
                                           conf["family"] + ".py"))
        assert conf["check"]["logit_err"] > 0
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        run.cell_of(spec, w["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(run.load_module(os.path.join(
            ROOT, "perfbench", "metrics", m["name"] + ".py")), "read")
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
