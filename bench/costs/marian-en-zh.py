"""Model flops of one served Marian request, counted from the sizes.

Encoder over the n source tokens: q, k, v, o projections (8 d^2 per
token), scores and weighted sum (4 n d per token), the ReLU feed-forward
(4 d d_ff per token).  Decoder, one step per output token at position t:
self-attention projections (8 d^2) and attention over t + 1 positions,
cross-attention q and o (4 d^2) and attention over n, the feed-forward,
and the output projection (2 d V); the cross-attention keys and values
of the source once per layer (4 n d^2).
"""


def request_flops(cfg, n: int, m: int) -> float:
    d, ff, v = cfg["d_model"], cfg["encoder_ffn_dim"], cfg["vocab_size"]
    enc = cfg["encoder_layers"] * n * (8 * d * d + 4 * n * d + 4 * d * ff)
    dec = cfg["decoder_layers"] * 4.0 * n * d * d
    for t in range(m):
        dec += cfg["decoder_layers"] * (12 * d * d + 4 * (t + 1) * d
                                        + 4 * n * d + 4 * d * ff)
        dec += 2 * d * v
    return float(enc + dec)
