"""Share of the traced window of the training cells in which no operation
ran on the device, in %."""
from lib import trace as TR


def read(record):
    if record.get("kind") != "fed_round" or "trace" not in record:
        return None
    lo, hi = TR.window(record["trace"])
    return 100.0 * (1.0 - TR.busy_seconds(record["trace"]) / (hi - lo))
