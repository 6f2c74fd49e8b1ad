"""Mellum2 served by the program against its plain reference
(``bench/plain/mellum2.py``) at a tiny shape on the CPU: 4 layers (3
sliding, 1 full), 8 experts top-2, a window of 16 under a 48-token
sequence, YaRN over an original context of 32 positions.

Logits are compared position by position: the prompt prefilled in chunks
through ``paged_step``, then the rest decoded through the pages, against
the reference's full forward pass.  Each mechanism the architecture adds,
put wrong in the program's place, fails the tolerance, and so do INT4
activations in place of the configuration's INT8."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench import model as bmodel  # noqa: E402
from bench.reference import _rms  # noqa: E402

CONF = "mellum2-12b-a2.5b-w4a8"
TINY = dict(hidden_size=128, moe_intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            num_hidden_layers=4, vocab_size=512, num_experts=8,
            num_experts_per_tok=2, sliding_window=16)
PROMPT, TOTAL, CHUNK, PAGE = 40, 48, 16, 8
# f32 program against the f32 reference at HIGHEST: the same arithmetic in
# another order (paged attention over gathered pages, experts contracted
# by group); the reading is under 1e-5, and a mechanism left out moves
# some logit by more than 2
F32_TOL = 1e-3
# the served W4A8 program (INT4 codes as stored, INT8 ABFP activations):
# each projection input rounds by up to 1/254 of its group's max, and near
# ties among the routed experts flip with it, so the mean |logit gap| is
# compared; the reading is about 0.18, INT4 activations give about 0.66
SERVED_TOL = 0.3


def _conf():
    conf = json.loads((ROOT / f"bench/configs/{CONF}.json").read_text())
    conf.update(TINY)
    conf["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    return conf


@pytest.fixture(scope="module")
def tiny():
    conf = _conf()
    plain = harness.architecture(conf["plain"])
    w = bmodel.make_weights(plain, conf, 11)
    ids = np.random.default_rng(3).integers(
        0, TINY["vocab_size"], TOTAL).astype(np.int32)
    ref = plain.Reference(conf, w, block=64)
    x = ref._hidden(ids, "f32")[:TOTAL]
    want = np.asarray(jnp.dot(_rms(x, w["final_norm"], "f32"), w["lm_head"],
                              precision="highest"))[:, :TINY["vocab_size"]]
    return conf, plain, w, ids, want


def _served_logits(cfg, params, policy, ids, compress=False):
    """Logits at every position: prefill chunks, then paged decode."""
    from repro.models import build_model
    from repro.models import serving_transforms as st

    model = build_model(cfg)
    if compress:
        params = st.compress_weights(params, policy)
        policy = st.serving_policy(policy)
    pages = -(-TOTAL // PAGE)
    state = model.init_paged_state(1, page_size=PAGE, n_pages=pages + 1,
                                   max_pages_per_seq=pages, kv="fp")
    state = state._replace(pages=state.pages._replace(
        table=jnp.arange(pages, dtype=jnp.int32)[None]))
    out = []
    for s in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - s)
        tok = np.zeros((1, CHUNK), np.int32)
        tok[0, :n] = ids[s:s + n]
        lg, state = model.paged_step(params, jnp.asarray(tok), state,
                                     n_valid=jnp.asarray([n]),
                                     policy=policy, all_logits=True)
        out.append(np.asarray(lg[0, :n]))
    for t in range(PROMPT, TOTAL):
        lg, state = model.paged_step(params, jnp.asarray(ids[t][None, None]),
                                     state, n_valid=jnp.asarray([1]),
                                     policy=policy)
        out.append(np.asarray(lg))
    return np.concatenate(out)[:, :TINY["vocab_size"]]


def _gap(conf, plain, w, ids, want, policy="fp32", compress=False,
         **wrong):
    from repro.core.policy import preset

    cfg = plain.arch_config(conf).replace(**wrong)
    got = _served_logits(cfg, plain.program_params(w), preset(policy), ids,
                         compress)
    return np.abs(got - want)


def test_prefill_then_paged_decode_match_the_reference(tiny):
    conf, plain, w, ids, want = tiny
    assert PROMPT > TINY["sliding_window"] and TOTAL > 32
    assert _gap(conf, plain, w, ids, want).max() < F32_TOL


def test_served_w4a8_matches_the_reference(tiny):
    conf, plain, w, ids, want = tiny
    assert _gap(conf, plain, w, ids, want, "w4a8_abfp",
                compress=True).mean() < SERVED_TOL


def _dropping_route(route):
    """GShard's capacity: each expert takes at most T * k / E rows in token
    order, and the rest of its rows are dropped (weight 0)."""
    def routed(self, x2, router):
        probs, gate, idx = route(self, x2, router)
        onehot = jax.nn.one_hot(idx.reshape(-1), self.n_experts,
                                dtype=jnp.int32)
        rank = ((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(1)
        cap = x2.shape[0] * self.top_k // self.n_experts
        return probs, jnp.where(rank.reshape(gate.shape) < cap, gate,
                                0.0), idx
    return routed


@pytest.mark.parametrize("wrong", ["default rope for yarn",
                                   "top-k weights not renormalised",
                                   "no window", "capacity-dropping routing"])
def test_a_wrong_mechanism_in_place_fails(tiny, wrong, monkeypatch):
    conf, plain, w, ids, want = tiny
    if wrong == "capacity-dropping routing":
        from repro.nn.moe import MoE

        monkeypatch.setattr(MoE, "route", _dropping_route(MoE.route))
    kw = {"default rope for yarn": {"yarn": None},
          "top-k weights not renormalised": {"norm_topk_prob": False},
          "no window": {"window": 1 << 20},
          "capacity-dropping routing": {}}[wrong]
    assert _gap(conf, plain, w, ids, want, **kw).max() > 10 * F32_TOL


def test_int4_activations_in_place_fail(tiny):
    conf, plain, w, ids, want = tiny
    assert _gap(conf, plain, w, ids, want, "w4a4_abfp",
                compress=True).mean() > SERVED_TOL


def test_counter_needs_windowed_context_and_eight_experts():
    """The counter adds attention over each query's live context, a
    sliding layer's window at most, and top_k experts per valid row."""
    conf = json.loads((ROOT / f"bench/configs/{CONF}.json").read_text())
    plain = harness.architecture(conf["plain"])
    c = plain.work_counter(conf)
    c.decode(1999)
    D, F, H, hd, KV = 2304, 896, 32, 128, 4
    attn = 4 * H * hd * (3 * 1024 + 2000)
    proj = D * hd * (H + 2 * KV) + H * hd * D
    experts = 4 * 2 * 8 * 3 * D * F
    assert c.model_flops == attn + 4 * 2 * (proj + D * 64) + experts \
        + 2 * D * 98304
    assert c.kernels["quant_matmul_grouped"][0] == experts
    assert c.kernels["flash_attention_quant"][0] == attn
