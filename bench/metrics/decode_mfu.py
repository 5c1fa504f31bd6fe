"""Model FLOPs of the decode and prefill work handed out in the traced
window (the model module's ``decode_token_flops`` and ``prefill_flops``:
2 FLOPs per parameter per token, plus attention over the positions each
token attends) over the summed wall time of the window's
``engine.step()`` calls times the chip's bf16 peak, in %.  It divides by
the engine's busy time, not by the window, because at a fixed arrival
rate the window's work is fixed."""


def read(record):
    if record.get("kind") != "serve" or not record.get("step_s"):
        return None
    return 100.0 * record["model_flops"] / (
        record["step_s"] * record["peak"]["bf16_flops_per_s"])
