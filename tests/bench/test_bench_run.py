"""A whole benchmark run on the CPU at a tiny size, with the chip check
skipped: sound, with a served token altered where it is produced, and with
the reference's lower-precision control in the program's place; and
``bench/run.py`` without a TPU."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench import harness  # noqa: E402

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)
# the widest gap sound tiny runs read: W4A8 0.1-0.2 against the a4
# control's 2-3; bf16 about 0.005 against the fp8 control's 0.08
TINY_LIMIT = {"qwen2-7b-w4a8": 0.6, "granite-3-8b-widths-bf16": 0.03}


def _cell(loop="closed", config="qwen2-7b-w4a8"):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    conf["model"].update(TINY)
    conf["serving"].update(n_slots=4)
    if conf["serving"]["attn_backend"] != "auto":
        conf["serving"]["attn_backend"] = "ref"
    mix = json.loads((ROOT / "bench/traffic/batch.json").read_text())
    mix.update(loop=loop, rate_per_s=20.0, max_len=256, block=16,
               prompt={"dist": "lognormal", "median": 40, "sigma": 0.7,
                       "min": 8, "max": 128},
               output={"dist": "lognormal", "median": 16, "sigma": 0.6,
                       "min": 4, "max": 64})
    if loop == "resident":
        mix["prompt"] = {"dist": "loguniform", "min": 60, "max": 120}
    return harness.Cell(
        name="tiny", chips=1, conf=conf,
        plain=harness.architecture(conf["plain"]), mix=mix,
        check={"number": "max_gap", "limit": TINY_LIMIT[config],
               "sample_tokens": 120},
        end_to_end=[m for m in bench["end_to_end"]
                    if m["name"] != "ttft_p90_ms"],
        per_layer=[])


def _run(cell, seed, **kw):
    dev = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return harness.run_cell(cell, seed, 1.5, False, device=dev,
                            t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("loop,config", [
    ("closed", "qwen2-7b-w4a8"), ("open", "qwen2-7b-w4a8"),
    ("resident", "qwen2-7b-w4a8"), ("closed", "granite-3-8b-widths-bf16")])
def test_sound_run_is_correct_and_reports_its_metrics(loop, config):
    res = _run(_cell(loop, config), 2**31 + 17)
    limit = TINY_LIMIT[config]
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]["max_gap"]["value"] < limit


def test_altered_token_is_not_correct():
    def fault(engine):
        step = engine._step

        def altered(*args):
            tok, state, keys = step(*args)
            return (tok + 1) % 512, state, keys

        engine._step = altered

    res = _run(_cell(), 23, fault=fault)
    assert not res["correct"]
    assert res["compared"]["max_gap"]["value"] > TINY_LIMIT["qwen2-7b-w4a8"]


@pytest.mark.parametrize("config,seed", [
    ("qwen2-7b-w4a8", 3), ("qwen2-7b-w4a8", 2**31 + 3),
    ("granite-3-8b-widths-bf16", 5)])
def test_lower_precision_control_in_place_is_not_correct(config, seed):
    cell = _cell(config=config)
    control = cell.conf["control"]
    res = _run(cell, seed, control=control)
    assert not res["correct"]
    got = res["compared"]["max_gap"]["value"]
    program = res["control"]["program"]["max_gap"]
    assert res["control"]["in_place"] == control
    assert got > TINY_LIMIT[config] > program and got >= 3 * program


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen2-7b-w4a8.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
