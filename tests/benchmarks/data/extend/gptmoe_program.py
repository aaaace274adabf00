"""A program file as a ``model_config`` PR would add one: the program's
``GPTModel`` with every second block's feed-forward swapped for four
routed experts (``models/gpt.py`` ``moe_experts``, ``moe_every``), so
other leaves and other counts than ``gpt2_program``'s.
``test_perfbench_extend.py`` copies it beside the configurations of a
copied tree; the benchmark itself does not use it."""
from __future__ import annotations

from harness import weights

TOP_K = 2      # MoELayer's default


def _is_moe(dims, i):
    return (i + 1) % dims["moe_every"] == 0


def build(cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTModel
    dims = cfg["dims"]
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = GPTModel(num_layers=dims["num_layers"],
                     hidden_size=dims["hidden_size"],
                     num_heads=dims["num_heads"],
                     vocab_size=dims["vocab_size"],
                     max_position=dims["max_position"],
                     moe_experts=dims["moe_experts"],
                     moe_every=dims["moe_every"],
                     **cfg.get("model_options", {}))
    model.to(dtype=cfg["dtype"])
    return weights.fill_model(model, seed, leaf_specs(dims), cfg["dtype"])


def leaf_specs(dims):
    d, f, e = (dims["hidden_size"], dims["ffn_hidden_size"],
               dims["moe_experts"])
    out = [("embeddings.word_embeddings.weight",
            (dims["vocab_size"], d), "normal"),
           ("embeddings.position_embeddings.weight",
            (dims["max_position"], d), "normal")]
    for i in range(dims["num_layers"]):
        p = f"blocks.{i}."
        out += [(p + "ln1.weight", (d,), "gain"),
                (p + "ln1.bias", (d,), "normal"),
                (p + "attn.qkv_proj.weight", (d, 3 * d), "normal"),
                (p + "attn.qkv_proj.bias", (3 * d,), "normal"),
                (p + "attn.out_proj.weight", (d, d), "normal"),
                (p + "attn.out_proj.bias", (d,), "normal"),
                (p + "ln2.weight", (d,), "gain"),
                (p + "ln2.bias", (d,), "normal")]
        if _is_moe(dims, i):
            out += [(p + "mlp.gate", (d, e), "normal"),
                    (p + "mlp.experts.w1", (e, d, f), "normal"),
                    (p + "mlp.experts.b1", (e, f), "normal"),
                    (p + "mlp.experts.w2", (e, f, d), "normal"),
                    (p + "mlp.experts.b2", (e, d), "normal")]
        else:
            out += [(p + "mlp.fc1.weight", (d, f), "normal"),
                    (p + "mlp.fc1.bias", (f,), "normal"),
                    (p + "mlp.fc2.weight", (f, d), "normal"),
                    (p + "mlp.fc2.bias", (d,), "normal")]
    out += [("head.ln_f.weight", (d,), "gain"),
            ("head.ln_f.bias", (d,), "normal"),
            ("head.lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


def matmul_params(dims):
    """Parameters a token is multiplied by: attention and the head as in
    a dense model; in an expert block the router and TOP_K of the
    experts, not all of them."""
    d, f, e = (dims["hidden_size"], dims["ffn_hidden_size"],
               dims["moe_experts"])
    total = d * dims["vocab_size"]
    for i in range(dims["num_layers"]):
        total += 4 * d * d
        total += (d * e + TOP_K * 2 * d * f) if _is_moe(dims, i) \
            else 2 * d * f
    return total


def train_flops_per_token(dims, seq_len):
    return (6 * matmul_params(dims)
            + 12 * dims["num_layers"] * dims["hidden_size"] * seq_len)


def train_least_seconds(cfg, peaks, work):
    return (work["steps"] * work["batch"] * work["seq_len"]
            * train_flops_per_token(cfg["dims"], work["seq_len"])
            / peaks["bf16_flops"])
