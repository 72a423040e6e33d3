"""Operations and bytes one step of a Llama-style decoder needs, from its
shapes (Hugging Face ``config.json`` names).

A step serves one token for each of ``batch`` sequences at position ``t``:
it reads every weight once (of the embedding only the ``batch`` rows it
looks up), reads the ``t`` keys and values already cached for each
sequence and writes one more.  Operations are two per multiply-add of the
matrix products and of attention over the ``t + 1`` valid positions;
norms, rotary embedding and softmax are left out.
"""


def _matmul_params(c: dict) -> int:
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def weight_bytes(c: dict, itemsize: int = 2) -> int:
    """All weights, the embedding and the norm scales included."""
    d, n = c["hidden_size"], c["num_hidden_layers"]
    return itemsize * (_matmul_params(c) + c["vocab_size"] * d + 2 * n * d + d)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return itemsize * 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"]


def step_flops(c: dict, batch: int, t: int) -> float:
    attn = 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] * (t + 1)
    return float(batch * (2 * _matmul_params(c) + attn))


def step_bytes(c: dict, batch: int, t: int, itemsize: int = 2) -> float:
    d = c["hidden_size"]
    touched = weight_bytes(c, itemsize) - itemsize * (c["vocab_size"] - batch) * d
    return float(touched + batch * (t + 1) * kv_bytes_per_token(c, itemsize))
