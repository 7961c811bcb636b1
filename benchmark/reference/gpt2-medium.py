"""The plain reference of configuration ``gpt2-medium``: GPT-2's forward and
loss in float32 ``jax.numpy`` (``_gpt2.py``, beside this file), and the one
place that says how the published sizes become the program's settings."""

from _gpt2 import logits, loss, tree  # noqa: F401  (the reference's API)


def program_config(sizes):
    """Keyword arguments of the program's ``GPTConfig`` for these sizes."""
    assert sizes["model_type"] == "gpt2"
    assert sizes["activation_function"] == "gelu_new"
    assert sizes["n_embd"] % sizes["n_head"] == 0
    return dict(
        vocab_size=sizes["vocab_size"], num_layers=sizes["n_layer"],
        num_heads=sizes["n_head"],
        head_dim=sizes["n_embd"] // sizes["n_head"],
        hidden_size=sizes["n_embd"], mlp_ratio=4,
        activation="gelu",              # the program's name for gelu_new
        norm_eps=sizes["layer_norm_epsilon"],
        qkv_bias=True, attn_out_bias=True, mlp_bias=True,
        tie_embeddings=bool(sizes["tie_word_embeddings"]))
