"""Sharding rules, elastic restore planning, gradient compression.

Multi-device cases run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the main pytest process keeps
the single real CPU device (smoke tests depend on it)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd


# ------------------------------------------------------------------ spec_for
class _FakeMesh:
    axis_names = ("pod", "data", "model")


def test_spec_resolution_default_rules():
    rules = dict(shd.DEFAULT_RULES)
    spec = shd.spec_for(("batch", None, "embed"), rules=rules,
                        mesh=_FakeMesh())
    assert spec == P(("pod", "data"), None, None)


def test_spec_drops_absent_mesh_axes():
    class SP:
        axis_names = ("data", "model")

    rules = dict(shd.DEFAULT_RULES)
    spec = shd.spec_for(("batch", "heads"), rules=rules, mesh=SP())
    # 'pod' silently dropped on the single-pod mesh
    assert spec == P(("data",), "model")


def test_spec_no_duplicate_axis_use():
    rules = dict(shd.DEFAULT_RULES, seq="model")
    spec = shd.spec_for(("seq", "heads"), rules=rules, mesh=_FakeMesh())
    # 'model' appears once; the later dim loses it
    flat = []
    for e in spec:
        if e is None:
            continue
        flat.extend(e if isinstance(e, tuple) else (e,))
    assert flat.count("model") == 1


def test_constrain_noop_outside_mesh():
    import jax.numpy as jnp

    x = jnp.ones((2, 2))
    y = shd.constrain(x, ("batch", "embed"))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- elastic restore plan
class _FakeMesh24:
    """2x4 (data, model) mesh stand-in: planning is pure, no devices needed."""

    axis_names = ("data", "model")

    class devices:
        shape = (2, 4)


def test_restore_specs_replication_fallback():
    from repro.dist.elastic import restore_specs

    paxes = {
        "wi": ("embed", "mlp"),  # d_ff=130 can't shard 4-way over 'model'
        "wo": ("mlp", "embed"),
        "bias": ("mlp",),
    }
    sds = {
        "wi": jax.ShapeDtypeStruct((64, 130), np.float32),
        "wo": jax.ShapeDtypeStruct((130, 64), np.float32),
        "bias": jax.ShapeDtypeStruct((128,), np.float32),
    }
    rules = dict(shd.DEFAULT_RULES)
    specs, report = restore_specs(paxes, sds, _FakeMesh24(), rules)
    assert specs["wi"] == P(None, None)  # fell back
    assert specs["wo"] == P(None, None)
    assert specs["bias"] == P("model")  # 128 % 4 == 0: stays sharded
    assert report.n_params == 3
    assert report.n_sharded == 1
    assert len(report.fallbacks) == 2
    fb = {f.path: f for f in report.fallbacks}
    assert fb["['wi']"].logical == "mlp"
    assert fb["['wi']"].size == 130 and fb["['wi']"].ways == 4


def test_restore_specs_rank_mismatch_bails_to_replicated():
    from repro.dist.elastic import restore_specs

    paxes = {"w": ("embed", "mlp")}
    sds = {"w": jax.ShapeDtypeStruct((8,), np.float32)}  # rank 1 != 2
    specs, report = restore_specs(paxes, sds, _FakeMesh24(),
                                  dict(shd.DEFAULT_RULES))
    assert specs["w"] == P()
    assert len(report.fallbacks) == 1 and report.fallbacks[0].dim == -1
    assert "1 replication fallbacks" in report.summary()


def test_restore_specs_tuple_rule_keeps_dividing_subset():
    """batch=6 divides data=2 but not (data, model)=8: keep the greedy
    dividing subset (same fit_axes policy as launch.specs.fit_batch_rule)
    and record the degradation."""
    from repro.dist.elastic import restore_specs

    rules = dict(shd.DEFAULT_RULES, batch=("data", "model"))
    paxes = {"x": ("batch", "embed")}
    sds = {"x": jax.ShapeDtypeStruct((6, 64), np.float32)}
    specs, report = restore_specs(paxes, sds, _FakeMesh24(), rules)
    assert specs["x"] == P(("data",), None)
    fb = report.fallbacks[0]
    assert fb.ways == 8 and fb.kept == 2


def test_restore_specs_unfit_dim_releases_axis_to_later_dim():
    """('experts', 'moe_mlp') both mapped to 'model': experts=6 can't divide
    model=4, so the fit must *release* the axis for the big moe_mlp dim
    instead of stranding it (first-dim-wins only applies among dims that
    actually fit)."""
    from repro.dist.elastic import restore_specs

    rules = dict(shd.DEFAULT_RULES, moe_mlp="model")
    paxes = {"wi": ("experts", "moe_mlp")}
    sds = {"wi": jax.ShapeDtypeStruct((6, 1024), np.float32)}
    specs, report = restore_specs(paxes, sds, _FakeMesh24(), rules)
    assert specs["wi"] == P(None, "model")
    assert len(report.fallbacks) == 1
    fb = report.fallbacks[0]
    assert fb.logical == "experts" and fb.ways == 4 and fb.kept == 1


def test_restore_specs_none_axes_replicates_without_fallback():
    """axes=None (unannotated leaf, axes_of convention) is intentional full
    replication — no Fallback, matching launch.specs.shardings_from_axes."""
    from repro.dist.elastic import restore_specs

    paxes = {"w": None}
    sds = {"w": jax.ShapeDtypeStruct((4, 4), np.float32)}
    specs, report = restore_specs(paxes, sds, _FakeMesh24(),
                                  dict(shd.DEFAULT_RULES))
    assert specs["w"] == P()
    assert report.n_params == 1 and not report.fallbacks


def test_shardings_for_restore_real_mesh_roundtrip(tmp_path):
    """End-to-end on the real single-device mesh: plan, save, restore."""
    from repro.checkpoint import store
    from repro.dist.elastic import shardings_for_restore

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = {"wi": np.arange(12, dtype=np.float32).reshape(3, 4)}
    paxes = {"wi": ("embed", "mlp")}
    store.save_pytree(str(tmp_path), 0, params)
    store.mark_committed(str(tmp_path), 0)
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    sh, report = shardings_for_restore(paxes, sds, mesh,
                                       dict(shd.DEFAULT_RULES))
    assert report.n_params == 1 and not report.fallbacks
    restored = store.restore_pytree(str(tmp_path), 0, sds, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["wi"]), params["wi"])


# --------------------------------------------------------- subprocess harness
def run_in_devices(code: str, n: int = 8) -> dict:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        {textwrap.indent(textwrap.dedent(code), '        ').strip()}
        print("RESULT:" + json.dumps(result))
    """)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/tmp"),
             # forced host-platform devices are a CPU feature; without this
             # a libtpu wheel in the image hijacks (and stalls) backend init
             "JAX_PLATFORMS": "cpu"},
        cwd=repo_root, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.slow
def test_train_step_shards_on_debug_mesh():
    """jit(train_step) with logical-rule shardings on a 2x4 mesh: runs,
    loss finite, params actually sharded over 'model'."""
    result = run_in_devices("""
        from repro.configs import get_config
        from repro.models import build_model
        from repro.nn.module import unbox, axes_of
        from repro.core.policy import preset
        from repro.optim.adamw import AdamW
        from repro.train.step import make_train_step, TrainStepConfig
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import specs as sp
        from repro.dist import sharding as shd

        cfg = get_config("opt-tiny").replace(
            n_layers=2, d_model=64, n_heads=4, n_kv=4, head_dim=16,
            d_ff=128, vocab=512, scan_layers=True)
        model = build_model(cfg)
        mesh = make_debug_mesh(2, 4)
        rules = dict(shd.DEFAULT_RULES)
        boxes = model.init(jax.random.PRNGKey(0))
        params, paxes = unbox(boxes), axes_of(boxes)
        psh = sp.shardings_from_axes(paxes, mesh, rules)
        params = jax.device_put(params, psh)
        opt = AdamW(lr=1e-3)
        ost = opt.init(params)
        step = make_train_step(model, opt, preset("w4a8_abfp").with_ste(True),
                               TrainStepConfig())
        batch = {
            "tokens": jnp.ones((8, 32), jnp.int32),
            "labels": jnp.ones((8, 32), jnp.int32),
        }
        bsh = sp.shardings_from_axes(
            {"tokens": ("batch", None), "labels": ("batch", None)},
            mesh, rules)
        batch = jax.device_put(batch, bsh)
        with mesh, shd.use_rules(mesh, rules):
            p2, o2, m = jax.jit(step)(params, ost, batch)
        wi = p2["blocks"]["ffn"]["wi"]["kernel"]
        # slice objects are only hashable on py3.12+; key on their bounds
        n_shards = len({
            tuple((sl.start, sl.stop) for sl in s.index)
            for s in wi.addressable_shards})
        result = {"loss": float(m["loss"]), "wi_shards": n_shards}
    """)
    assert np.isfinite(result["loss"])
    assert result["wi_shards"] >= 4  # sharded over model axis


@pytest.mark.slow
def test_gradient_compression_pod_allreduce():
    """int8-compressed psum over the 'pod' axis: mean error small, error
    feedback carries the residual."""
    result = run_in_devices("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.compression import compressed_psum_pod

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 256))
        e0 = jnp.zeros((1, 256))

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("pod"), P()), out_specs=(P(), P("pod")),
                 check_vma=False)
        def run(gl, el):
            red, enew = compressed_psum_pod(gl[0], el[0], mesh)
            return red[None] / 1.0, enew[None]

        red, enew = run(g, jnp.broadcast_to(e0, (2, 256)))
        true_mean = g.mean(axis=0)
        err = float(jnp.abs(red[0] - true_mean).max())
        scale = float(jnp.abs(g).max()) / 127
        result = {"err": err, "tol": 2.1 * scale,
                  "efb_nonzero": bool(jnp.abs(enew).max() > 0)}
    """)
    assert result["err"] <= result["tol"]
    assert result["efb_nonzero"]


@pytest.mark.slow
def test_elastic_restore_onto_different_mesh(tmp_path):
    """Checkpoint saved unsharded restores onto a 2x4 mesh with computed
    shardings; uneven dims fall back to replication with a report."""
    tmp_path = str(tmp_path)
    result = run_in_devices(f"""
        from repro.checkpoint import store
        from repro.configs import get_config
        from repro.models import build_model
        from repro.nn.module import unbox, axes_of
        from repro.dist.elastic import shardings_for_restore
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_debug_mesh

        cfg = get_config("opt-tiny").replace(
            n_layers=2, d_model=64, n_heads=4, n_kv=4, head_dim=16,
            d_ff=130,  # 130 % 4 != 0 -> mlp dim must fall back
            vocab=512)
        model = build_model(cfg)
        boxes = model.init(jax.random.PRNGKey(0))
        params, paxes = unbox(boxes), axes_of(boxes)
        store.save_pytree({tmp_path!r}, 1, params)
        store.mark_committed({tmp_path!r}, 1)

        mesh = make_debug_mesh(2, 4)
        sds = jax.eval_shape(lambda: params)
        sh, report = shardings_for_restore(paxes, sds, mesh,
                                           dict(shd.DEFAULT_RULES))
        restored = store.restore_pytree({tmp_path!r}, 1, sds, shardings=sh)
        wi = restored["blocks"][0]["ffn"]["wi"]["kernel"]
        ok = bool(jnp.allclose(wi, params["blocks"][0]["ffn"]["wi"]["kernel"]))
        result = {{"ok": ok, "fallbacks": len(report.fallbacks),
                  "n": report.n_params}}
    """)
    assert result["ok"]
    assert result["fallbacks"] > 0  # d_ff=130 can't shard 4-way


def test_policy_presets():
    from repro.core.policy import preset

    p = preset("w4a8_abfp")
    assert p.input.fmt_name == "int8" and p.weight.fmt_name == "int4"
    assert p.attn_bmm
    q = preset("w4a8_abfp_qat")
    assert q.input.ste and q.weight.ste
    assert preset("fp32").enabled is False
    n128 = preset("w4a4_abfp", n=128)
    assert n128.input.group == 128
    with pytest.raises(ValueError):
        preset("bogus")


def test_policy_hashable_jit_static():
    """Policies close over jitted fns (frozen dataclass hashability)."""
    from repro.core.policy import preset

    {preset("w4a8_abfp"): 1}  # hashable
    assert preset("w4a8_abfp") == preset("w4a8_abfp")
