#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e and print their memory.

    JAX_PLATFORMS=cpu python3 bench/aot_memory.py --workload <cell>

Needs no chip: the TPU compiler compiles for a topology it is only told
about, from shapes alone.  Prints ``memory_analysis()`` of the weight
generator and of the prefill-chunk and decode step programs at the cell's
sizes, and the bytes of the served weights and KV pages, so a cell's slots
and pages can be sized before it reaches the chip.  The step is the
engine's (``PagedServeEngine._step_fn``: the model's ``paged_step``, then
sampling) over the weights the engine serves.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import load_cell
    from repro.core.policy import preset, with_attn_backend
    from repro.models import build_model
    from repro.models import serving_transforms as st
    from repro.serve import steps
    from repro.serve.kv_pages import pages_for

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_cell(args.workload)
    conf, mix, s = cell.conf, cell.mix, cell.conf["serving"]
    n_slots = mix.get("n_slots", s["n_slots"])
    plain = cell.plain
    cfg = plain.arch_config(conf)
    model = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes}, outputs "
              f"{m.output_size_in_bytes}, temporaries "
              f"{m.temp_size_in_bytes}, aliased {m.alias_size_in_bytes}",
              flush=True)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    make = jax.jit(lambda k: plain.program_params(plain.weights_fn(conf)(k)))
    report("weights", make.lower(key).compile())
    dense = jax.eval_shape(make, key)
    policy = preset(s["policy"], n_layers=cfg.n_layers)
    if s["attn_backend"] != "auto":
        policy = with_attn_backend(policy, s["attn_backend"])
    served = dense
    if s["compress"]:
        served = jax.eval_shape(lambda p: st.compress_weights(p, policy),
                                dense)
        policy = st.serving_policy(policy)
    ps = s["page_size"]
    n_pages = n_slots * pages_for(mix["max_len"], ps)
    state = jax.eval_shape(lambda: model.init_paged_state(
        n_slots, page_size=ps, n_pages=n_pages,
        max_pages_per_seq=pages_for(mix["max_len"], ps), kv=s["kv"]))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                           for a in jax.tree.leaves(t))
    print(f"dense f32 weights {nbytes(dense)}, served weights "
          f"{nbytes(served)}, paged state {nbytes(state)} "
          f"({n_slots} slots x max_len {mix['max_len']}, {n_pages} pages)",
          flush=True)

    def step(params, tokens, state, n_valid, keys, temps, topk):
        logits, state = model.paged_step(params, tokens, state,
                                         n_valid=n_valid, policy=policy)
        toks, keys = steps.sample_step(logits, keys, temps, topk)
        return toks[:, 0], state, keys

    chunk = max(ps, -(-64 // ps) * ps)
    for name, width in (("prefill", chunk), ("decode", 1)):
        shapes = on_chip((served, jax.ShapeDtypeStruct((n_slots, width),
                                                       jnp.int32),
                          state, jax.ShapeDtypeStruct((n_slots,), jnp.int32),
                          jax.ShapeDtypeStruct((n_slots, 2), jnp.uint32),
                          jax.ShapeDtypeStruct((n_slots,), jnp.float32),
                          jax.ShapeDtypeStruct((n_slots,), jnp.int32)))
        report(f"{name} step", jax.jit(step).lower(*shapes).compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
