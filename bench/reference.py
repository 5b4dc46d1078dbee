"""Plain float32 reference of a decoder's training step.

The trainer (``train_readings``, ``compare``) serves every configuration:
AdamW with global-norm clipping and a warm-up + cosine learning rate over
the loss of the module that the configuration's ``reference`` key names
(``bench/<reference>.py``, which has ``loss_fn(conf, params, tokens, matmul,
fault)``), from the weights of its family (``bench/families/``).

This module is also the reference of dense decoders (``"reference":
"reference"``), written from the published model descriptions (Qwen2 / Qwen3
in Hugging Face ``transformers``): RMSNorm, rotary embeddings on the two
halves of each head, grouped-query causal attention with optional q/k/v bias
and per-head q/k RMSNorm, a SwiGLU MLP, a tied or untied head and mean
next-token cross entropy. Another architecture's reference can reuse its
sublayers. It imports nothing of the program and takes nothing the program
made: weights and batches come from the benchmark's own code.

Every matrix product runs at ``highest`` precision. ``matmul="fp8"`` runs
them on per-tensor-scaled float8_e4m3fn operands in both passes instead: the
control, one precision step below the bf16 the configurations state.
``fault="half_batch"`` takes the loss over half of the batch (half the rows,
or half the positions of a single row): a fault the comparison must catch.
Every reference honours both.

Memory: layers are rematerialised one by one, attention runs in blocks of
query rows and the head + loss in blocks of positions, so the 8-layer cells
fit one chip beside AdamW's state.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import model

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024  # query rows per attention block
LOSS_BLOCK = 1024  # positions per head + loss block
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


@dataclasses.dataclass
class Readings:
    """What a training run is compared on: each step's loss, the first
    step's clipped gradient and the change of the parameters over the run,
    as per-leaf (per-layer for stacked leaves) norms."""

    losses: list[float]
    grad_norms: dict[str, float]
    update_norms: dict[str, float]


def _q8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / FP8_MAX, 1e-30)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _einsum(spec: str, matmul: str):
    plain = functools.partial(jnp.einsum, spec, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
    if matmul == "f32":
        return plain

    @jax.custom_vjp
    def mm(a, b):
        return plain(_q8(a), _q8(b))

    def fwd(a, b):
        qa, qb = _q8(a), _q8(b)
        return plain(qa, qb), (qa, qb)

    def bwd(res, ct):
        return jax.vjp(plain, *res)[1](_q8(ct))

    mm.defvjp(fwd, bwd)
    return mm


def mm_for(matmul: str):
    """``mm(spec)(a, b)``: an einsum at ``highest`` precision, or on fp8
    operands where ``matmul="fp8"``."""
    return lambda spec: _einsum(spec, matmul)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (b, s, heads, hd); rotate (x1, x2) halves by position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, mm):
    """Causal GQA attention, blocked over query rows. q (b,s,h,hd), k/v (b,s,kh,hd)."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    outs = []
    for i0 in range(0, s, Q_BLOCK):
        i1 = min(i0 + Q_BLOCK, s)
        scores = mm("bqhd,bkhd->bhqk")(q[:, i0:i1], k[:, :i1]) * hd**-0.5
        allowed = jnp.arange(i1)[None, :] <= jnp.arange(i0, i1)[:, None]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        outs.append(mm("bhqk,bkhd->bqhd")(probs, v[:, :i1]))
    return jnp.concatenate(outs, axis=1)


def attention_sublayer(conf, mm, x, p):
    """x plus the attention of its RMSNorm (``ln1``, ``mixer``)."""
    b, s, d = x.shape
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps, a = conf["rms_norm_eps"], p["mixer"]
    hin = rms(x, p["ln1"]["scale"], eps)
    q, k, v = (mm("bsd,de->bse")(hin, a[w]) for w in ("wq", "wk", "wv"))
    if conf["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = q.reshape(b, s, h, hd), k.reshape(b, s, kh, hd), v.reshape(b, s, kh, hd)
    if conf["qk_norm"]:
        q, k = rms(q, a["q_norm"], eps), rms(k, a["k_norm"], eps)
    theta = float(conf["rope_theta"])
    o = _attention(_rope(q, theta), _rope(k, theta), v, mm).reshape(b, s, h * hd)
    return x + mm("bse,ed->bsd")(o, a["wo"])


def swiglu(mm, h, m):
    """SwiGLU of h (..., d) under ``wi_gate``, ``wi_up`` and ``wo``."""
    gate = jax.nn.silu(mm("bsd,df->bsf")(h, m["wi_gate"]))
    up = mm("bsd,df->bsf")(h, m["wi_up"])
    return mm("bsf,fd->bsd")(gate * up, m["wo"])


def _layer(conf, mm, x, p):
    x = attention_sublayer(conf, mm, x, p)
    return x + swiglu(mm, rms(x, p["ln2"]["scale"], conf["rms_norm_eps"]), p["mlp"])


def loss_fn(conf, params, tokens, matmul="f32", fault=None):
    """Mean next-token cross entropy of ``tokens`` (b, s) under ``params``."""
    mm = mm_for(matmul)
    x = params["embed"]["tok"][tokens]

    def body(x, p):
        return jax.checkpoint(functools.partial(_layer, conf, mm))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["pos0"])
    return head_loss(conf, mm, params, x, tokens, fault)


def head_loss(conf, mm, params, x, tokens, fault=None):
    """Mean next-token cross entropy from the last layer's output x (b, s, d):
    the final RMSNorm, the tied or untied head, and ``fault``."""
    x = rms(x, params["final_norm"]["scale"], conf["rms_norm_eps"])
    b, s, d = x.shape
    if conf["tie_word_embeddings"]:
        head, spec = params["embed"]["tok"], "nd,vd->nv"
    else:
        head, spec = params["lm_head"]["w"], "nd,dv->nv"
    x, tgt = x[:, :-1], tokens[:, 1:]
    if fault == "half_batch":
        x, tgt = (x[: b // 2], tgt[: b // 2]) if b > 1 else (x[:, : (s - 1) // 2], tgt[:, : (s - 1) // 2])
    x, tgt = x.reshape(-1, d), tgt.reshape(-1)

    @jax.checkpoint
    def block_nll(xb, tb):
        logits = mm(spec)(xb, head)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    n = x.shape[0]
    total = sum(block_nll(x[i:i + LOSS_BLOCK], tgt[i:i + LOSS_BLOCK])
                for i in range(0, n, LOSS_BLOCK))
    return total / n


def learning_rate(opt: dict, count):
    c = count.astype(jnp.float32)
    warm = jnp.minimum(c / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = jnp.clip((c - opt["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(math.pi * prog))
    return opt["learning_rate"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def _train_step(conf, opt, loss_of, matmul, fault, params, mu, nu, count, tokens):
    loss, grads = jax.value_and_grad(
        lambda p: loss_of(conf, p, tokens, matmul, fault))(params)
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    count = count + 1
    lr = learning_rate(opt, count)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, count, loss, model.leaf_norms(grads)


def train_readings(conf: dict, opt: dict, seed: int, batches: list[np.ndarray],
                   device=None, matmul: str = "f32", fault=None) -> Readings:
    """Train from the seed's weights (the configuration's family) over
    ``batches`` on one device under its reference's loss, and read the
    losses, the first clipped gradient and the change of the weights."""
    device = device or jax.devices()[0]
    key = model.params_key(seed)
    init_params = model.family(conf).init_params
    loss_of = importlib.import_module(f"bench.{conf['reference']}").loss_fn
    with jax.default_matmul_precision("highest"), jax.default_device(device):
        init = jax.jit(functools.partial(init_params, conf))
        params = init(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.int32)
        step = jax.jit(functools.partial(_train_step, conf, opt, loss_of, matmul, fault),
                       donate_argnums=(0, 1, 2))
        losses, grad_norms = [], None
        for tokens in batches:
            params, mu, nu, count, loss, gn = step(params, mu, nu, count, jnp.asarray(tokens))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = model.flat_norms(jax.device_get(gn))
        del mu, nu
        delta = jax.jit(lambda p, k: model.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, init_params(conf, k))))
        update_norms = model.flat_norms(jax.device_get(delta(params, key)))
    return Readings(losses, grad_norms, update_norms)


def compare(prog: Readings, ref: Readings) -> dict[str, float]:
    """The numbers a cell's ``correct`` is decided on.

    loss_gap: the largest |loss - reference loss| over the compared steps.
    grad_norm_gap, update_norm_gap: over leaves, the largest gap between the
    program's norm and the reference's, against the larger of that leaf's
    reference norm and the median leaf's. The change leaves out leaves whose
    reference gradient is under a thousandth of the median leaf's (AdamW
    moves those by round-off alone: a key's bias under softmax).
    """
    if len(prog.losses) != len(ref.losses):
        raise ValueError(f"{len(prog.losses)} program steps, {len(ref.losses)} reference steps")
    if prog.grad_norms.keys() != ref.grad_norms.keys():
        raise ValueError("program and reference leaves differ")

    def worst(p, r, keys):
        med = float(np.median([r[k] for k in keys]))
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    g_med = float(np.median(list(ref.grad_norms.values())))
    moving = [k for k, g in ref.grad_norms.items() if g >= 1e-3 * g_med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog.losses, ref.losses)),
        "grad_norm_gap": worst(prog.grad_norms, ref.grad_norms, list(ref.grad_norms)),
        "update_norm_gap": worst(prog.update_norms, ref.update_norms, moving),
    }
