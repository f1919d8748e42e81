"""Parameters and model FLOPs per token, from a configuration's sizes: the
benchmark's own arithmetic (the program's ``utils/telemetry`` 6N estimate
and its peak table are not read).

FLOPs are what the forward and backward passes REQUIRE: 2 per
multiply-add, backward = 2 x forward, attention scores and weighted sum at
the causal half (a token attends to (s + 1) / 2 positions on average),
recomputation not counted, embedding lookups and elementwise work not
counted."""


def param_count(m: dict) -> int:
    h, ffn, L = m["hidden_size"], m["ffn_hidden_size"], m["num_layers"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * ffn + ffn) + (ffn * h + h) + 4 * h
    return (m["vocab_size"] * h + m["max_position_embeddings"] * h
            + L * per_layer + 2 * h)


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    h, ffn, L = m["hidden_size"], m["ffn_hidden_size"], m["num_layers"]
    matmul = 2 * (3 * h * h + h * h + 2 * h * ffn)       # qkv, out, fc_in, fc_out
    attention = 2 * 2 * h * (seq_len + 1) / 2            # q.k and p.v, causal half
    head = 2 * h * m["vocab_size"]                       # tied LM head
    return L * (matmul + attention) + head


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len)
