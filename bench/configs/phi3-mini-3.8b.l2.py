"""phi3-mini-3.8b.l2 as the benchmark runs it: Phi-3-mini at every
published width with 2 of its 32 layers, the benchmark's weights in the
program's parameter layout, the model FLOPs per example, and the feed's
batch rebuilt for the check.  The plain reference is beside this file.

The published ``sliding_window`` of 2047 has no effect at the 1024-token
sequences the cells run; the program has no window."""
from __future__ import annotations

from dataclasses import replace

import numpy as np

from bench import feed


def arch(conf: dict):
    """The registry's ArchConfig with every size the file states."""
    from repro.configs import get_arch
    from repro.models.transformer import padded_vocab
    a = replace(get_arch(conf["registry"]), n_layers=conf["num_hidden_layers"],
                d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
                n_heads=conf["num_attention_heads"],
                n_kv_heads=conf["num_key_value_heads"],
                head_dim=conf["head_dim"], vocab=conf["vocab_size"],
                rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
                tie_embeddings=conf["tie_word_embeddings"])
    if (padded_vocab(a.vocab) != conf["padded_vocab"] or a.mlp_act != "swiglu"
            or conf["hidden_act"] != "silu" or a.rotary_pct != 1.0
            or a.qk_norm or a.family != "dense"):
        raise ValueError(f"{conf['name']}: the program's {a} is not the file")
    return a


def layout(conf: dict, mix: dict) -> dict:
    pd = mix["param_dtype"]
    d, f, L = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    h = conf["num_attention_heads"] * conf["head_dim"]
    Vp = conf["padded_vocab"]
    mat = lambda i, o: ((L, i, o), pd, 1.0 / np.sqrt(i))
    vec = ((L, d), "float32", "ones")
    return {
        "embed": ((Vp, d), pd, 0.02),
        "prelude": [],
        "blocks": ({"ln1": vec,
                    "attn": {"wq": mat(d, h), "wk": mat(d, h),
                             "wv": mat(d, h), "wo": mat(h, d)},
                    "ln2": vec,
                    "mlp": {"w1": mat(d, f), "w3": mat(d, f),
                            "w2": mat(f, d)}},),
        "final_norm": ((d,), "float32", "ones"),
        "head": ((d, Vp), pd, 1.0 / np.sqrt(d)),
    }


def flops_per_example(conf: dict, mix: dict) -> float:
    """6 x matmul parameters (embedding lookup excluded, head at the
    published vocabulary) x tokens, plus 12 x layers x d_model x T per
    token (PaLM, appendix B)."""
    d, f, L = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    h = conf["num_attention_heads"] * conf["head_dim"]
    T = mix["seq_len"]
    per_token = L * (4 * d * h + 3 * d * f) + d * conf["vocab_size"]
    return float(6 * per_token * T + 12 * L * h * T * T)


def batch(conf: dict, mix: dict, seed: int, step: int) -> dict:
    return feed.token_batch(seed, step, mix, conf["vocab_size"])
