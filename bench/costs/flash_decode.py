"""Operations and bytes of one ``flash_decode`` call, from its shapes.

q (B, H, D) attends to the first ``lengths[b]`` positions of a key and a
value cache (B, S, Hkv, D).  What the algorithm needs: scores and the
weighted sum over the valid positions (4 flops per position, head and
channel), reading q, the valid keys and values once, writing the output.
"""

from typing import Sequence


def cost(heads: int, kv_heads: int, head_dim: int, lengths: Sequence[int],
         itemsize: int = 4):
    valid = float(sum(lengths))
    b = len(lengths)
    flops = 4.0 * valid * heads * head_dim
    nbytes = itemsize * (2 * b * heads * head_dim
                         + 2 * valid * kv_heads * head_dim)
    return flops, nbytes
