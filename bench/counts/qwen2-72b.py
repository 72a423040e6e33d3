"""Operations and bytes of one chip's share of a step of a Qwen2 decoder
served tensor-parallel over ``tensor_parallel`` chips, from its shapes
(Hugging Face ``config.json`` names).

A step serves one token for each of ``batch`` sequences at position ``t``.
The whole step reads every weight once (of the embedding only the ``batch``
rows it looks up), reads the ``t`` keys and values already cached for each
sequence and writes one more; its operations are two per multiply-add of
the matrix products and of attention over the ``t + 1`` valid positions
(norms, biases, rotary embedding and softmax left out).  One chip's share is
that over ``tensor_parallel``, whatever the layout: the weights a layout
repeats on every chip (``wk``/``wv``) are counted once, so a floor from
these counts is one the chip can reach under any layout.  The time the
chips spend exchanging partial sums over the interconnect is not in the
floor either.
"""


def _tp(c: dict) -> int:
    return c["tensor_parallel"]


def _layer_params(c: dict) -> int:
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    bias = (hq + 2 * hkv) * hd if c["qkv_bias"] else 0
    return attn + bias + 3 * d * f + 2 * d


def params(c: dict) -> int:
    """Parameters of the whole configuration: layers, embedding, head and
    final norm."""
    d, v = c["hidden_size"], c["vocab_size"]
    return c["num_hidden_layers"] * _layer_params(c) + 2 * v * d + d


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    """One chip's share of all weights."""
    return itemsize * params(c) / _tp(c)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> float:
    """One chip's share of a token's keys and values over all layers."""
    n, hkv, hd = c["num_hidden_layers"], c["num_key_value_heads"], c["head_dim"]
    return itemsize * 2 * n * hkv * hd / _tp(c)


def step_flops(c: dict, batch: int, t: int) -> float:
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    matmul = (c["num_hidden_layers"] * (d * hq * hd + 2 * d * hkv * hd
                                        + hq * hd * d + 3 * d * f)
              + d * c["vocab_size"])
    attn = 4 * c["num_hidden_layers"] * hq * hd * (t + 1)
    return float(batch * (2 * matmul + attn)) / _tp(c)


def step_bytes(c: dict, batch: int, t: int, itemsize: int = 2) -> float:
    d = c["hidden_size"]
    unread = itemsize * (c["vocab_size"] - batch) * d / _tp(c)
    return float(weight_bytes(c, itemsize) - unread
                 + batch * (t + 1) * kv_bytes_per_token(c, itemsize))
