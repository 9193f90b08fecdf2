"""Training operations per token of a dense decoder-only LM with grouped
query attention and a SwiGLU MLP, from its published shapes alone.

Forward: two operations per weight of every matrix product a token goes
through (the attention projections, the three MLP matrices and the
output head; the embedding is a lookup) and, per layer, 2 x 2 x heads x
head_dim per key attended for the scores and the weighted values. Under
a causal mask with a sliding window of `window` keys, query i attends
min(i + 1, window) keys; the count takes the mean over a sequence of
`seq` positions. Backward: twice the forward. Recomputation does not
count: the result is the work the step needs, not the work it does.
"""


def flops_per_token(layers: int, d_model: int, heads: int, kv_heads: int,
                    head_dim: int, d_ff: int, vocab: int, seq: int,
                    window: int = 0) -> float:
    """Forward plus backward operations per trained token."""
    attn = (2 * heads + 2 * kv_heads) * d_model * head_dim
    mlp = 3 * d_model * d_ff
    weights = layers * (attn + mlp) + d_model * vocab
    keys = sum(min(i + 1, window or seq) for i in range(seq)) / seq
    forward = 2 * weights + layers * 4 * heads * head_dim * keys
    return 3 * forward
