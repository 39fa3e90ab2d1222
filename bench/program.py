"""The system under test, as the benchmark drives it: the model built from
a configuration file, the serving engine and the jitted train step. This
is the only module that imports the program (`repro`)."""
from __future__ import annotations

import dataclasses


# published key -> the program's ModelConfig field, and how to read it.
# Several published names for one quantity map to the same field.
PUBLISHED = {
    "vocab_size": ("vocab_size", int),
    "hidden_size": ("d_model", int),
    "num_hidden_layers": ("n_layers", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "rms_norm_eps": ("norm_eps", float),
    "rope_theta": ("rope_theta", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
    "num_experts": ("n_experts", int),
    "n_routed_experts": ("n_experts", int),
    "num_experts_per_tok": ("moe_top_k", int),
    "moe_intermediate_size": ("d_ff_expert", int),
    "n_shared_experts": ("n_shared_experts", int),
    "first_k_dense_replace": ("first_k_dense", int),
    "torch_dtype": ("param_dtype", str),
}
# published hidden_act -> the program's `mlp_act` that computes it (its
# other MLP, an ungated tanh-approximated GELU, is no published one)
MLP_ACT = {"silu": "swiglu"}


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the program's
    own preset for `run.arch`; every published key of `PUBLISHED` the file
    has; then `run.program`, fields the published file cannot state (a
    layer pattern, say); and the attention operator from `run.attention`.
    What the file states and the preset cannot do is an error."""
    from repro.attention import AttentionSpec
    from repro.configs import get_config

    run = cfg["run"]
    base = get_config(run["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {field: read(cfg[key]) for key, (field, read) in PUBLISHED.items()
            if key in cfg}
    over["activ_dtype"] = over.get("param_dtype", base.activ_dtype)
    extra = run.get("program", {})
    unknown = set(extra) - fields
    if unknown:
        raise ValueError(f"run.program names no ModelConfig field: "
                         f"{sorted(unknown)}")
    # JSON lists are the config's tuples (a layer pattern)
    over.update({k: tuple(v) if isinstance(v, list) else v
                 for k, v in extra.items()})
    mcfg = dataclasses.replace(base, **over, chunk_size=int(run["chunk_size"]),
                               attn=AttentionSpec.parse(run["attention"]))
    if MLP_ACT.get(cfg["hidden_act"]) != mcfg.mlp_act:
        raise ValueError(f"hidden_act {cfg['hidden_act']!r} is not the "
                         f"preset's MLP {mcfg.mlp_act!r}")
    if bool(cfg["attention_bias"]) != mcfg.qkv_bias:
        raise ValueError(f"attention_bias {cfg['attention_bias']} is not the "
                         f"preset's qkv_bias {mcfg.qkv_bias}")
    return mcfg


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
