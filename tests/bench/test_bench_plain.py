"""Architecture modules (``bench/plain/<name>.py``), named by a
configuration's ``"plain"`` key.

The decoder's weights, reference gaps and work counts are pinned to what
the benchmark gave before the dense decoder moved behind the hook, at
fixed seeds.  A second architecture, written with its configuration,
mix, limits and metric readers into a directory of its own, runs through
``load_cell``, ``run_cell`` and ``correct.check`` with no file under
``bench/`` edited."""

import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench import model as bmodel  # noqa: E402

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)
QWEN, GRANITE = "qwen2-7b-w4a8", "granite-3-8b-widths-bf16"
CONTRACT = ("arch_config", "weights_fn", "program_params", "Reference",
            "work_counter")

# sha256 of the plain tree (each leaf's name, dtype, shape and bytes in
# key order) and of the program's tree (its leaves' bytes in tree order)
DIGESTS = {
    (QWEN, 7): (
        "00e520c8befa43ae9df65f46708c4ee6b537de700a98914fd10ca9b1696a7629",
        "1c9259c1ef238a620a3bc917557fbe283ef74472ec3ad8f2666e15f043f5e374"),
    (QWEN, 2**31 + 17): (
        "fbaead87da5dd71163c61912050ebd4454d56435ed25ad82c29abe7d8b98f5b3",
        "a9dc3686efcdadd32d8b6ce412e48e209603e24516c635cd8e4e578e30b7a530"),
    (GRANITE, 7): (
        "3e7a235391c8cf7c1a0e3d46803331c5523919264cc71134eeb3024946239fa9",
        "fdfa52acb8b944c93494bf8e7046167fa8f26504396af0129fdc4f213bd28ea2"),
    (GRANITE, 2**31 + 17): (
        "12581f6cffd2a690ca30cc4ea5424630ef834acb6109f5c17c1cd9079d3eeb96",
        "63c962c6b8023666131c028e6f11cc5077f5d8d24aa0060dd9c4ef96465af4f2"),
}
# gaps of the f32 reference and of its control's picks (weights of seed
# 11, blocks of 64 rows; a prompt of 21 and 9 served tokens drawn from
# default_rng(5))
GAPS = {
    QWEN: ([4.672731876373291, 3.051025867462158, 3.9491381645202637,
            5.457859039306641, 5.082921981811523, 4.8747053146362305,
            3.739448070526123, 3.6981143951416016, 5.547237396240234],
           [0.6717698574066162, 0.666405439376831, 0.3555774688720703,
            0.0, 0.010128021240234375, 0.0, 0.0, 0.2175595760345459, 0.0]),
    GRANITE: ([0.525423526763916, 0.41173458099365234, 0.5642511248588562,
               0.7368427515029907, 0.5577600002288818, 0.47813844680786133,
               0.3191094696521759, 0.6276440620422363, 0.7153270244598389],
              [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.002151668071746826, 0.0,
               0.04220247268676758]),
}
# (model FLOPs, attention FLOPs, attention kernel bytes) of the calls in
# _feed, at the tiny sizes and at the files' own
WORK = {
    (QWEN, "tiny"): (110628864, 11800576, 2001760),
    (QWEN, "file"): (342100770816, 660832256, 44807552),
    (GRANITE, "tiny"): (110628864, 11800576, 6995296),
    (GRANITE, "file"): (648768266240, 1888092160, 584690560),
}


def _conf(name, tiny=True):
    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    if tiny:
        conf["model"].update(TINY)
    return conf


def _digest(w):
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        for part in (k, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _feed(counter):
    counter.prefill(0, 64, emits=False)
    counter.prefill(64, 100, emits=True)
    for pos in range(100, 140):
        counter.decode(pos)
    counter.prefill(0, 17, emits=True)
    counter.decode(1500)
    return counter


@pytest.mark.parametrize("name", [QWEN, GRANITE])
def test_config_names_an_architecture_with_the_whole_contract(name):
    conf = _conf(name, tiny=False)
    mod = harness.architecture(conf["plain"])
    assert all(callable(getattr(mod, f)) for f in CONTRACT)
    assert harness.architecture(conf["plain"]) is mod  # loaded once


def test_a_config_without_an_architecture_is_refused(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = _conf(QWEN, tiny=False)
    del conf["plain"]
    (tmp_path / "c.json").write_text(json.dumps(conf))
    bench["configs"][0]["file"] = "c.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="names no architecture"):
        harness.load_cell(bench["workloads"][0]["name"], root=tmp_path)


@pytest.mark.parametrize("name,seed", sorted(DIGESTS))
def test_decoder_weights_are_the_same_bit_for_bit(name, seed):
    conf = _conf(name)
    plain = harness.architecture(conf["plain"])
    w = bmodel.make_weights(plain, conf, seed)
    tree = plain.program_params(w)
    program = hashlib.sha256(b"".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(tree)))
    assert (_digest(w), program.hexdigest()) == DIGESTS[name, seed]


@pytest.mark.parametrize("name", [QWEN, GRANITE])
def test_decoder_reference_gives_the_same_gaps(name):
    conf = _conf(name)
    plain = harness.architecture(conf["plain"])
    ref = plain.Reference(conf, bmodel.make_weights(plain, conf, 11),
                          block=64)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, 21).astype(np.int32)
    served = [int(t) for t in rng.integers(0, 512, 9)]
    got = ref.judge(prompt, served, control=conf["control"])
    gap, control_gap = GAPS[name]
    # the same program on the same CPU gives the same bits; the tolerance
    # is f32 rounding on another CPU's instruction set
    np.testing.assert_allclose(got["gap"], gap, rtol=0, atol=1e-5)
    np.testing.assert_allclose(got["control_gap"], control_gap, rtol=0,
                               atol=1e-5)


@pytest.mark.parametrize("name,size", sorted(WORK))
def test_decoder_counts_the_same_work(name, size):
    conf = _conf(name, tiny=size == "tiny")
    plain = harness.architecture(conf["plain"])
    model_flops, attn_flops, attn_bytes = WORK[name, size]
    counter = _feed(plain.work_counter(conf))
    assert counter.model_flops == model_flops
    kernel = conf["attention_kernel"]
    assert counter.kernels == (
        {kernel: [attn_flops, attn_bytes]} if kernel else {})
    # the attention arithmetic holds for a configuration that names no
    # kernel too, once a kernel is named
    named = _feed(plain.WorkCounter(plain.shape(conf), "attention"))
    assert named.kernels == {"attention": [attn_flops, attn_bytes]}


# a second architecture, in files of its own: the decoder, whose counter
# also counts its readout as a kernel, read by a metric of its own
TOY = '''"""A test-only architecture: the decoder, with the readout counted
as a kernel of its own."""

from bench.harness import architecture

dec = architecture("decoder")
arch_config = dec.arch_config
weights_fn = dec.weights_fn
program_params = dec.program_params
Reference = dec.Reference


class Counter(dec.WorkCounter):
    def __init__(self, conf):
        super().__init__(dec.shape(conf), conf.get("attention_kernel"))
        self.kernels["readout"] = [0, 0]

    def _readout(self):
        k = self.kernels["readout"]
        k[0] += dec.readout_flops(self.s)
        k[1] += 4 * self.s.d_model * self.s.vocab

    def prefill(self, start, stop, emits):
        super().prefill(start, stop, emits)
        if emits:
            self._readout()

    def decode(self, pos):
        super().decode(pos)
        self._readout()


def work_counter(conf):
    return Counter(conf)
'''
READOUT_METRIC = '''def read(r):
    return r.work.kernels["readout"][0] or None
'''


def _toy_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    bench = root / "bench"
    for d in ("plain", "configs", "traffic", "cells", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "plain" / "toy.py").write_text(TOY)
    conf = _conf(QWEN)
    conf.update(name="toy", plain="toy")
    conf["serving"].update(n_slots=4, attn_backend="ref")
    (bench / "configs" / "toy.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/batch.json").read_text())
    mix.update(max_len=256, block=16,
               prompt={"dist": "lognormal", "median": 40, "sigma": 0.7,
                       "min": 8, "max": 128},
               output={"dist": "lognormal", "median": 16, "sigma": 0.6,
                       "min": 4, "max": 64})
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    (bench / "cells" / "toy.tiny.json").write_text(json.dumps(
        {"number": "max_gap", "limit": 0.6, "sample_tokens": 120}))
    e2e = ["output_tok_s", "itl_p95_ms", "setup_s"]
    for m in e2e:
        shutil.copy(ROOT / f"bench/metrics/{m}.py", bench / "metrics")
    (bench / "metrics" / "readout_flops.py").write_text(READOUT_METRIC)
    spec = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.tiny", "config": "toy",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": [{"name": m, "unit": "x", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}
                       for m in e2e],
        "per_layer": [{"name": "readout_flops", "unit": "flops",
                       "better": "higher", "source": "program_counter",
                       "layer": "step", "moves": "output_tok_s"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_new_architecture_is_new_files_only(tmp_path):
    before = {p: p.stat().st_mtime_ns for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = _toy_root(tmp_path)
    cell = harness.load_cell("toy.tiny", root=root)
    assert cell.plain.__file__ == str(root / "bench/plain/toy.py")
    dev = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

    def run(traced, **kw):
        return harness.run_cell(cell, 2**31 + 41, 1.5, traced, device=dev,
                                t_start=time.perf_counter(), **kw)

    sound = run(True)
    assert sound["correct"], sound["compared"]
    # the toy counter's own kernel reaches its own metric reader
    assert sound["metrics"]["readout_flops"]["value"] > 0
    control = run(False, control=cell.conf["control"])
    assert not control["correct"]
    assert control["compared"]["max_gap"]["value"] > 0.6 > \
        control["control"]["program"]["max_gap"]
    after = {p: p.stat().st_mtime_ns for p in (ROOT / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before
