"""Model FLOPs utilisation of the whole training step: model FLOPs per
token (``costs.train_flops_per_token``) times the window's tokens per
second, over chips times the chip's bf16 peak, in percent."""


def read(ctx):
    c = ctx["costs"]
    flops = c.train_flops_per_token(ctx["config"], ctx["job"]["seq_len"])
    return (100.0 * flops * ctx["tokens_per_s"]
            / (ctx["chips"] * ctx["peaks"]["bf16_flops"]))
