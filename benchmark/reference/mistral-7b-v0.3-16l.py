"""The plain reference of configuration ``mistral-7b-v0.3-16l``: Mistral-7B's forward and
loss in float32 ``jax.numpy`` (``_mistral.py``, beside this file; shared
with the other depth of the same family), and the one place that says how the
published sizes become the program's settings."""

from _mistral import logits, loss, tree  # noqa: F401  (the reference's API)


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "mistral" and sizes["hidden_act"] == "silu"
    assert sizes["sliding_window"] is None
    assert sizes["hidden_size"] % sizes["num_attention_heads"] == 0
    return dict(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        hidden_size=sizes["hidden_size"],
        mlp_dim_override=sizes["intermediate_size"],
        use_rope=True, rope_theta=sizes["rope_theta"], use_rmsnorm=True,
        norm_eps=sizes["rms_norm_eps"], gated_mlp=True, gate_act="silu",
        tie_embeddings=bool(sizes["tie_word_embeddings"]))
