"""What every architecture's weights and engine share.

A configuration file names its architecture (``"plain": "<name>"``), and
``bench/plain/<name>.py`` owns everything that knows that architecture's
names and widths: the program's ``ArchConfig`` (``arch_config``), the
plain weight tree (``weights_fn``), its renaming into the tree the
program's model takes (``program_params``), the plain reference
(``Reference``) and the count of needed work (``work_counter``).  This
module holds what those modules share: the seed's PRNG key, the exact
weight grids, one jitted call that makes a module's weights on the
device, and the program's paged engine over them.

Every value is exact in f32 whatever the compiler fuses: an integer drawn
from the seed times a power of two.  A configuration served in bfloat16
(``serving.dtype``) gets every leaf rounded to bfloat16 where it is made,
so the program and the reference read the very same values.
``uniform_grid`` weights are uniform on a 2^23-step grid (finer than bf16
everywhere, so one bf16 MXU pass rounds nearly every weight);
``int4_abfp_grid`` weights are INT4 codes times a power-of-two scale per
group of ``group`` rows along the contraction and per output column, with
a code of +-7 in every group, so ABFP INT4 compression (scale = group max
/ 7, kept in bf16) stores them without loss: a served INT4 checkpoint, as
GPTQ and its kin produce.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

INT4_CODE_RMS = math.sqrt(sum(k * k for k in range(-7, 8)) / 15)


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def served_dtype(conf: dict) -> str:
    """The dtype of the served weights, activations and fp pages."""
    return conf["serving"].get("dtype", "float32")


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def _pow2(e):
    """2.0 ** e for int32 ``e``, built from its exponent bits (exact)."""
    return jax.lax.bitcast_convert_type(
        ((e + 127) << 23).astype(jnp.int32), jnp.float32)


def _bits(key, shp):
    return jax.random.bits(key, shp, jnp.uint32)


def _uniform_grid(key, shp, std: float):
    """Uniform with standard deviation ~``std``, on a power-of-two grid."""
    step = 2.0 ** round(math.log2(math.sqrt(3) * std / 2**22))
    k = (_bits(key, shp) >> 9).astype(jnp.int32) - 2**22
    return k.astype(jnp.float32) * step


def _int4_grid(key, shp, group: int):
    """INT4 codes x a power-of-two scale per (group of rows, column); the
    first row of every group holds +-7, so each group's max is 7 x scale."""
    *lead, K, N = shp
    if K % group:
        raise ValueError(f"contraction {K} is no multiple of group {group}")
    kc, ke = jax.random.split(key)
    bits = _bits(kc, (*lead, K // group, group, N))
    codes = ((bits >> 8) % 15).astype(jnp.int32) - 7
    top = 7 * (2 * (bits & 1).astype(jnp.int32) - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, bits.shape, bits.ndim - 2)
    codes = jnp.where(row == 0, top, codes)
    e0 = round(math.log2(1 / (math.sqrt(K) * INT4_CODE_RMS)))
    e = e0 - 1 + (_bits(ke, (*lead, K // group, 1, N)) % 3).astype(jnp.int32)
    return (codes.astype(jnp.float32) * _pow2(e)).reshape(shp)


def _norm_grid(key, shp):
    """A norm's scale: 1 + k / 512 for k drawn from -64 .. 63."""
    k = (_bits(key, shp) >> 25).astype(jnp.int32) - 64
    return 1.0 + k.astype(jnp.float32) * 2.0 ** -9


def make_weights(plain, conf: dict, seed: int) -> dict:
    """The plain weight tree of ``plain.weights_fn``, made in one jitted
    call."""
    return jax.jit(plain.weights_fn(conf))(seed_key(seed))


def build_engine(conf: dict, cfg, params: dict, n_slots: int,
                 max_len: int):
    """The program's paged engine over ``params``, as the configuration
    states it: policy, compression, page storage, attention backend."""
    from repro.core.policy import preset, with_attn_backend
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import PagedServeEngine

    s = conf["serving"]
    model = build_model(cfg)
    want = jax.tree.structure(unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0))))
    if jax.tree.structure(params) != want:
        raise ValueError(f"weight tree {jax.tree.structure(params)} is not "
                         f"the program's {want}")
    policy = preset(s["policy"], n_layers=cfg.n_layers)
    if s["attn_backend"] != "auto":
        policy = with_attn_backend(policy, s["attn_backend"])
    return PagedServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                            policy=policy, compress=s["compress"],
                            page_size=s["page_size"], kv=s["kv"])
