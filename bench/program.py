"""The system under test, as the benchmark drives it: the model built from
a configuration file, the serving engine and the jitted train step. This
is the only module that imports the program (`repro`)."""
from __future__ import annotations

import dataclasses


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the program's
    own preset for `run.arch`, with every published size taken from the
    file and the attention operator from `run.attention`."""
    from repro.attention import AttentionSpec
    from repro.configs import get_config

    run = cfg["run"]
    base = get_config(run["arch"])
    if cfg["hidden_act"] != "silu" or base.mlp_act != "swiglu":
        raise ValueError("the qwen3 path serves a SiLU-gated MLP only")
    if cfg["attention_bias"] or base.qkv_bias:
        raise ValueError("the qwen3 path has no attention bias")
    return dataclasses.replace(
        base, vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], activ_dtype=cfg["torch_dtype"],
        chunk_size=int(run["chunk_size"]),
        attn=AttentionSpec.parse(run["attention"]))


def abstract_params(mcfg):
    import jax

    from repro.models import init_model
    return jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), mcfg)[0])


def serve_engine(params, mcfg, run: dict):
    from repro.serve import ServeEngine
    return ServeEngine(params, mcfg, max_slots=int(run["max_slots"]),
                       max_len=int(run["max_len"]))


def finished_ok(fin) -> bool:
    from repro.serve import RequestStatus
    return fin.status is RequestStatus.FINISHED


def train_step(mcfg, n_params: int, opt: dict):
    """(opt_init, jitted step) as the program's training driver builds
    them: AdamW by the program's own policy, params and optimizer state
    donated."""
    import jax

    from repro.launch.steps import make_train_step, pick_optimizer
    name, optimizer = pick_optimizer(mcfg, n_params, lr=float(opt["lr"]),
                                     total_steps=int(opt["total_steps"]))
    if name != "adamw":
        raise ValueError(f"the program picked {name}, not adamw")
    step = jax.jit(make_train_step(mcfg, optimizer,
                                   clip_norm=float(opt["clip_norm"])),
                   donate_argnums=(0, 1))
    return optimizer[0], step


def compile_cache(path: str) -> None:
    """Point JAX's persistent cache (and the program's) at `path`, and
    cache every program however quickly it compiled."""
    import os

    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
