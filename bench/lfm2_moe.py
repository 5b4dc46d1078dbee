"""Plain float32 reference of LFM2-MoE's loss (``bench/families/lfm2_moe.py``),
written from the published description (Hugging Face ``transformers``'
``Lfm2Moe*`` modules):

- layer: ``x + op(operator_norm(x))``, then ``+ ffn(ffn_norm(.))``; a final
  norm precedes the tied head. Every norm is RMSNorm with ``norm_eps``.
- short conv: ``B, C, x = split3(in_proj(h))`` and
  ``out_proj(C * conv(B * x))``, ``conv`` causal and depthwise over
  ``conv_L_cache`` taps with no bias, written as explicit shifted sums.
- attention: grouped-query, per-head RMSNorm on q and k, then rotary
  embeddings on the whole head.
- dense layers: SwiGLU of ``intermediate_size``.
- expert layers: ``s = sigmoid(x W_r)`` over all of the router's experts;
  the top ``num_experts_per_tok`` by ``s + expert_bias`` are chosen and
  weighted by their ``s`` alone, divided by the chosen weights' sum + 1e-6
  and times ``routed_scaling_factor``. The experts held here (the
  configuration's ``num_experts`` from ``first_expert`` on) are computed
  densely on every token, each weighted by its gate (zero where not
  chosen); the others' part is left out, as in the program.

It imports nothing of the program. Every product runs at ``highest``
precision, or on fp8 operands under ``matmul="fp8"`` (``reference.mm_for``);
``fault="half_batch"`` is the trainer's (``reference.head_loss``). Memory:
each layer is rematerialised, attention runs in checkpointed blocks of query
rows and the experts in checkpointed blocks of positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.families import lfm2_moe as family

Q_BLOCK = 512  # query rows per attention block
E_BLOCK = 1024  # positions per expert block


def _dense_conf(conf: dict) -> dict:
    """The keys ``reference.head_loss`` reads, as LFM2 sets them."""
    return dict(conf, rms_norm_eps=conf["norm_eps"], tie_word_embeddings=True)


def short_conv(conf, mm, h, p):
    """The gated short convolution of h (b, s, d)."""
    b_gate, c_gate, x = (mm("bsd,de->bse")(h, p[w]) for w in ("wB", "wC", "wx"))
    bx = b_gate * x
    taps = p["conv_w"].shape[0]
    s = h.shape[1]
    conv = sum(p["conv_w"][i] * jnp.pad(bx, ((0, 0), (taps - 1 - i, 0), (0, 0)))[:, :s]
               for i in range(taps))
    return mm("bse,ed->bsd")(c_gate * conv, p["wo"])


def _rope(x, theta):
    """x: (b, s, heads, hd); rotate the halves (x1, x2) by position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(conf, mm, h, p):
    """Causal grouped-query attention of h (b, s, d) with q/k RMSNorm."""
    b, s, _ = h.shape
    nh, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], family.head_dim(conf)
    eps, theta = conf["norm_eps"], float(conf["rope_theta"])
    q, k, v = (mm("bsd,de->bse")(h, p[w]) for w in ("wq", "wk", "wv"))
    q = _rope(R.rms(q.reshape(b, s, nh, hd), p["q_norm"], eps), theta)
    k = _rope(R.rms(k.reshape(b, s, kh, hd), p["k_norm"], eps), theta)
    k, v = (jnp.repeat(t.reshape(b, s, kh, hd), nh // kh, axis=2) for t in (k, v))

    @functools.partial(jax.checkpoint, static_argnums=(0,))
    def rows(i0, qb, kb, vb):
        scores = mm("bqhd,bkhd->bhqk")(qb, kb) * hd**-0.5
        allowed = jnp.arange(kb.shape[1])[None, :] <= jnp.arange(i0, i0 + qb.shape[1])[:, None]
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd")(probs, vb)

    out = jnp.concatenate([rows(i0, q[:, i0:i0 + Q_BLOCK], k[:, :i0 + Q_BLOCK],
                                v[:, :i0 + Q_BLOCK]) for i0 in range(0, s, Q_BLOCK)], axis=1)
    return mm("bse,ed->bsd")(out.reshape(b, s, nh * hd), p["wo"])


def route(conf, mm, h, m, weigh_by=None):
    """Gates (b, s, held) of the held experts: the sigmoid router's top-k by
    score + bias, weighted by score. ``weigh_by="score_plus_bias"`` weights
    them by score + bias: a wrong router the program must fail."""
    k, first, held = conf["num_experts_per_tok"], conf["first_expert"], conf["num_experts"]
    scores = jax.nn.sigmoid(mm("bsd,de->bse")(h, m["router"]))
    biased = scores + m["expert_bias"]
    chosen = jax.lax.top_k(biased, k)[1]
    weights = jnp.take_along_axis(biased if weigh_by == "score_plus_bias" else scores,
                                  chosen, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * conf["routed_scaling_factor"]
    held_ids = first + jnp.arange(held)
    return jnp.sum((chosen[..., None] == held_ids) * weights[..., None], axis=-2)


def experts(conf, mm, h, m, weigh_by=None):
    """The held experts' gated sum for h (b, s, d), every expert on every
    position, in checkpointed blocks of positions."""
    gates = route(conf, mm, h, m, weigh_by)

    @jax.checkpoint
    def block(hb, gb):
        gate = jax.nn.silu(mm("bsd,edf->bsef")(hb, m["wi_gate"]))
        up = mm("bsd,edf->bsef")(hb, m["wi_up"])
        out = mm("bsef,efd->bsed")(gate * up, m["wo"])
        return mm("bse,bsed->bsd")(gb, out)

    s = h.shape[1]
    return jnp.concatenate([block(h[:, i:i + E_BLOCK], gates[:, i:i + E_BLOCK])
                            for i in range(0, s, E_BLOCK)], axis=1)


def _layer(conf, mm, mixer, mlp, weigh_by, x, p):
    eps = conf["norm_eps"]
    h = R.rms(x, p["ln1"]["scale"], eps)
    x = x + (attention if mixer == "attn" else short_conv)(conf, mm, h, p["mixer"])
    h = R.rms(x, p["ln2"]["scale"], eps)
    if mlp == "dense":
        return x + R.swiglu(mm, h, p["mlp"])
    return x + experts(conf, mm, h, p["mlp"], weigh_by)


def _layer_params(conf, params):
    """Each layer's weights, in order, from the parameter layout's stacks."""
    return [jax.tree_util.tree_map(lambda a: a[r], params["blocks"][key])
            for key, r in family.layer_slots(conf)]


def loss_fn(conf, params, tokens, matmul="f32", fault=None, weigh_by=None):
    """Mean next-token cross entropy of ``tokens`` (b, s) under ``params``."""
    mm = R.mm_for(matmul)
    x = params["embed"]["tok"][tokens]
    for (mixer, mlp), p in zip(family.layer_kinds(conf), _layer_params(conf, params)):
        x = jax.checkpoint(functools.partial(_layer, conf, mm, mixer, mlp, weigh_by))(x, p)
    return R.head_loss(_dense_conf(conf), mm, params, x, tokens, fault)
