"""gpt2: ``paddle_tpu.models.nlp.gpt.GPT`` under ``gpt_loss``."""
from benchmark.families import _recipe
from benchmark.reference import gpt2 as reference

valid_tokens = _recipe.full_rows


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"wte.weight": "wte", "wpe.weight": "wpe",
           "ln_f.weight": "ln_f.g", "ln_f.bias": "ln_f.b"}
    for i in range(cfg["n_layer"]):
        b, h = f"blocks.{i}.", f"h.{i}."
        out.update({
            b + "ln1.weight": h + "ln_1.g", b + "ln1.bias": h + "ln_1.b",
            b + "attn.qkv.weight": h + "attn.c_attn.w",
            b + "attn.qkv.bias": h + "attn.c_attn.b",
            b + "attn.proj.weight": h + "attn.c_proj.w",
            b + "attn.proj.bias": h + "attn.c_proj.b",
            b + "ln2.weight": h + "ln_2.g", b + "ln2.bias": h + "ln_2.b",
            b + "fc1.weight": h + "mlp.c_fc.w", b + "fc1.bias": h + "mlp.c_fc.b",
            b + "fc2.weight": h + "mlp.c_proj.w",
            b + "fc2.bias": h + "mlp.c_proj.b"})
    return out


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place. A configuration's
    ``program`` group goes to the program's own config as it stands (say
    ``use_recompute``), so that a switch of the program needs no edit here."""
    from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss

    if cfg["activation_function"] != "gelu_new" or \
            not cfg["tie_word_embeddings"] or cfg["layer_norm_epsilon"] != 1e-5:
        raise ValueError("the program's GPT is tanh-GELU, tied, eps 1e-5")
    model = GPT(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
        layers=cfg["n_layer"], heads=cfg["n_head"],
        max_seq=cfg["n_positions"], dropout=cfg["resid_pdrop"],
        initializer_range=cfg["initializer_range"],
        **cfg.get("program", {})))
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, gpt_loss, cfg["recipe"], mesh_axes)


def flops_per_position(cfg, length):
    return _recipe.palm_flops_per_position(
        _recipe.n_params(reference.param_specs(cfg)), cfg["n_layer"],
        cfg["n_embd"], length)


step_flops = _recipe.token_rows_step_flops(flops_per_position)
