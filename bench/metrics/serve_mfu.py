"""The whole model's share of the chip's bf16 peak over the traced serving
window, in %: the published model's operations for the tokens the window
processed, over the window's length and the peak (``bench/peaks.json``).

The count is the dense-equivalent model's, the same whatever implements
it (a compressed weight counts as the dense product it stands for; the
experts as the ``num_experts_per_tok`` a token is routed to), read from
the configuration's published keys: per token and layer the q, k, v and o
projections, the router, and ``num_experts_per_tok`` SwiGLU experts of
width ``intermediate_size``; per key a token attends to (itself included)
2·2·heads·head_dim for the scores and the weighted sum; per served token
the tied head, 2·d·vocab.  The tokens are the runner's count
(``window["work"]``): each prefill that served its first token in the
window with all its prompt tokens, and each running slot of each decode
step."""


def flops(conf: dict, work: dict) -> float:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    KV, L = conf["num_key_value_heads"], conf["num_hidden_layers"]
    hd = conf.get("head_dim") or d // H
    E, k = conf.get("num_local_experts", 0), conf.get("num_experts_per_tok", 0)
    ff = conf["intermediate_size"]
    proj = 2 * d * (2 * H * hd + 2 * KV * hd)
    mlp = (2 * d * E + k * 3 * 2 * d * ff) if E else 3 * 2 * d * ff
    per_token = L * (proj + mlp)
    per_key = L * 4 * H * hd
    head = 2 * d * conf["vocab_size"]
    return (work["tokens"] * per_token + work["context"] * per_key
            + work["rows"] * head)


def read(ctx):
    red, work = ctx["trace"], ctx["window"].get("work")
    if not work or not work["tokens"] or red.window_s <= 0 or not red.devices:
        return None
    total = flops(ctx["cell"].config, work)
    return 100.0 * total / red.window_s / ctx["peaks"]["bf16_flops_per_s"]
