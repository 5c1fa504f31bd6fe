"""Model FLOPs of the traced window's rounds (the model module's
``fed_round_flops`` per round) over the window's length times the chip's
bf16 peak, in %."""


def read(record):
    if record.get("kind") != "fed_round" or not record.get("steps"):
        return None
    return 100.0 * record["model_flops"] / (
        record["window_s"] * record["peak"]["bf16_flops_per_s"])
