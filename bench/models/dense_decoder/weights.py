"""A dense Qwen-style decoder's binding to the program, and its weights and
LoRA adapters, made by the benchmark from the run's seed. Program and
reference both take these; neither makes its own.

A configuration names this file as its ``weights``. What the harness calls:

* ``model_config(conf, prog)``: the program's ModelConfig for the
  configuration file, cut to its depth; raises where the file's widths or
  the program's parameter layout are not the ones made here;
* ``make_params(cfg, key)`` and ``make_adapters(cfg, key)``: every weight,
  and the LoRA adapters, in one jitted call on the device each.

The layout is the program's parameter tree for a dense decoder
(``models/model.py``); ``check_layout`` compares it with the program's own
``init_params`` / ``init_adapters`` by shape alone before anything is made.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp


def model_config(conf: Dict, prog):
    """The program's ModelConfig for a configuration file, cut to its
    depth; raises where the file's widths are not the program's."""
    get = prog.smoke_config if conf.get("smoke") else prog.get_config
    cfg = dataclasses.replace(get(conf["arch"]),
                              num_layers=conf["num_hidden_layers"])
    want = {"d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf["num_key_value_heads"],
            "vocab_size": conf["vocab_size"],
            "rope_theta": conf["rope_theta"],
            "norm_eps": conf["rms_norm_eps"], "act": conf["hidden_act"],
            "tie_embeddings": conf["tie_word_embeddings"],
            "head_dim": conf.get("head_dim", conf["hidden_size"]
                                 // conf["num_attention_heads"])}
    ft = conf["finetune"]
    want_lora = (ft["lora_rank"], ft["lora_alpha"], tuple(ft["targets"]))
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if (cfg.lora.rank, cfg.lora.alpha, tuple(cfg.lora.targets)) != want_lora:
        bad["lora"] = (cfg.lora, want_lora)
    if bad:
        raise ValueError(f"{conf['name']}: the program's {conf['arch']} "
                         f"differs from the file: {bad}")
    check_layout(cfg, prog.MD)
    return cfg


def dims(cfg) -> Dict[str, int]:
    return dict(L=cfg.num_layers, d=cfg.d_model, H=cfg.num_heads,
                KV=cfg.num_kv_heads, hd=cfg.head_dim, ff=cfg.d_ff,
                V=cfg.vocab_size)


def param_shapes(cfg) -> Dict:
    """{leaf: (shape, std)}; std None means a norm weight of ones."""
    n = dims(cfg)
    L, d, H, KV, hd, ff, V = (n[k] for k in ("L", "d", "H", "KV", "hd",
                                             "ff", "V"))
    attn = {"wq": ((L, d, H * hd), d ** -0.5),
            "wk": ((L, d, KV * hd), d ** -0.5),
            "wv": ((L, d, KV * hd), d ** -0.5),
            "wo": ((L, H * hd, d), (H * hd) ** -0.5)}
    if cfg.qk_norm:
        attn["q_norm"] = ((L, hd), None)
        attn["k_norm"] = ((L, hd), None)
    return {"embed": ((V, d), d ** -0.5), "final_norm": ((d,), None),
            "unembed": ((V, d), d ** -0.5),
            "scan": {"ln1": ((L, d), None), "attn": attn,
                     "ln2": ((L, d), None),
                     "mlp": {"gate": ((L, d, ff), d ** -0.5),
                             "up": ((L, d, ff), d ** -0.5),
                             "down": ((L, ff, d), ff ** -0.5)}}}


def lora_dims(cfg, targets) -> Dict[str, tuple]:
    n = dims(cfg)
    d, H, KV, hd, ff = n["d"], n["H"], n["KV"], n["hd"], n["ff"]
    all_dims = {"q": (d, H * hd), "k": (d, KV * hd), "v": (d, KV * hd),
                "o": (H * hd, d), "gate": (d, ff), "up": (d, ff),
                "down": (ff, d)}
    return {t: all_dims[t] for t in targets}


def _is_shape_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def check_layout(cfg, MD) -> None:
    """Raise unless the program's trees have exactly these leaves and
    shapes (dense decoders without pre/post layers only)."""
    want = jax.tree.map(lambda s: s[0], param_shapes(cfg),
                        is_leaf=_is_shape_leaf)
    want = dict(want, pre=[], post=[])
    got = jax.eval_shape(functools.partial(MD.init_params, cfg),
                         jax.random.PRNGKey(0))
    got = jax.tree.map(lambda s: tuple(s.shape), got)
    if got != want:
        raise ValueError(f"{cfg.name}: the program's parameter tree is not "
                         f"the dense-decoder layout the benchmark makes")
    ad = jax.eval_shape(functools.partial(MD.init_adapters, cfg),
                        jax.random.PRNGKey(0))
    ad = jax.tree.map(lambda s: tuple(s.shape), ad)
    r, L = cfg.lora.rank, cfg.num_layers
    want_ad = {"pre": [], "post": [], "scan": {
        t: {"a": (L, i, r), "b": (L, r, o)}
        for t, (i, o) in lora_dims(cfg, cfg.lora.targets).items()}}
    if ad != want_ad:
        raise ValueError(f"{cfg.name}: the program's adapter tree is not "
                         f"the layout the benchmark makes")


def make_params(cfg, key, dtype=jnp.bfloat16):
    """All weights in one jitted call on the device, in ``dtype``: normal
    draws scaled by 1/sqrt(fan-in) (1/sqrt(d) for the embedding tables),
    norm weights of ones."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape_leaf)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, std) in zip(keys, leaves):
            if std is None:
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32) * std
                            ).astype(dtype))
        return jax.tree.unflatten(tree, out)

    p = make(key)
    p["pre"], p["post"] = [], []
    return p


def make_adapters(cfg, key):
    """LoRA A ~ N(0, 1/fan-in), B = 0 (float32), stacked over layers."""
    r, L = cfg.lora.rank, cfg.num_layers
    ld = lora_dims(cfg, cfg.lora.targets)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(ld))
        return {t: {"a": jax.random.normal(k, (L, i, r), jnp.float32)
                    * i ** -0.5,
                    "b": jnp.zeros((L, r, o), jnp.float32)}
                for k, (t, (i, o)) in zip(keys, ld.items())}

    return {"pre": [], "scan": make(key), "post": []}

