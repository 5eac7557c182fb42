"""Model FLOPs of the work done in the window (prefills whose first token
came in it, decode tokens delivered in it) over the window and the chip's
bf16 peak, in %. Per token: two FLOPs per matmul weight (projections, MLP,
LM head); attention four per query head, channel and attended position:
every earlier prompt position in prefill, at most the token budget in
decode (FreeKV attends the resident set only)."""


def _per_layer_matmul(m):
    d, H, kv, dh, ff = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    return d * (H + 2 * kv) * dh + H * dh * d + 3 * d * ff


def read(run):
    m, w = run.model, run.window
    L, H, dh = m["num_hidden_layers"], m["num_attention_heads"], \
        m["head_dim"]
    budget = run.mix["serving"]["freekv"]["budget"]
    dense = 2 * (L * _per_layer_matmul(m) + m["hidden_size"]
                 * m["vocab_size"])
    attn = 4 * H * dh * L
    flops = 0.0
    for r in w.records.values():
        if r.first_t is not None and w.t_start <= r.first_t < w.t_end:
            T = r.prompt_len
            flops += T * dense + attn * T * (T + 1) / 2
        for i, t in enumerate(r.token_t[1:], start=1):
            if w.t_start <= t < w.t_end:
                flops += dense + attn * min(r.prompt_len + i, budget)
    return 100.0 * flops / ((w.t_end - w.t_start)
                            * run.peaks["bf16_flops_per_s"])
