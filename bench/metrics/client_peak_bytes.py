"""What one edge client must hold: ``memory_analysis()`` of the client
side of the round, compiled for a cohort of one at the cell's micro-batch
through the function the round itself uses (``_make_cohort_trajectory``
with one client): arguments + outputs + temporaries - aliased, in bytes.
The compiler's count, taken in the traced run after the window."""


def read(record):
    return record.get("client_peak_bytes")
