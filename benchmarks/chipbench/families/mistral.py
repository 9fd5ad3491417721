"""The family of ``"model_type": "mistral"``: what the windows and the readers need of
the model, by the names the README's contract gives. It holds no arithmetic of its own:
the equations, the seeded weights and the comparisons are ``reference.py``'s, the counts
``work.py``'s, and the program's side is ``models/llama.py`` (``LlamaConfig``,
``loss_fn``), which runs Mistral's sizes.
"""

from __future__ import annotations

from benchmarks.chipbench import reference
from benchmarks.chipbench.reference import (  # noqa: F401  (handed out)
    compare_serve, compare_train, gen_params, serve_reference, train_reference)
from benchmarks.chipbench.work import (  # noqa: F401  (handed out)
    flash_work, matmul_params, paged_attn_work, serve_flops_per_token,
    train_flops_per_token)


def program_config(c: dict, **over):
    """The configuration file's sizes as the program's own config object."""
    from accelerate_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim_override=c["head_dim"], d_ff=c["intermediate_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        max_seq=c["max_position_embeddings"], sliding_window=c["sliding_window"],
        tie_embeddings=c["tie_word_embeddings"], scan_layers=True, **over)


def loss(params, batch: dict, cfg):
    """The program's training loss on one batch ``{"tokens": [B, S+1]}``."""
    from accelerate_tpu.models import llama

    return llama.loss_fn(params, batch, cfg)


def leaf_norms(tree) -> dict:
    """‖leaf‖ by the leaf names ``train_reference`` uses."""
    return reference._flat(reference.leaf_norms(tree))


def change_norms(params, c: dict, seed: int) -> dict:
    """‖p − p0‖ by leaf name; p0 is made again from the seed."""
    return reference._flat(reference.change_norms(
        params, reference.seed_key(seed), reference.freeze(c)))
