"""Real tokens over padded slots of the window's batches, counted by the
benchmark on the host batches (exact for a seed)."""

UNIT = "%"
BETTER = "higher"
LAYER = "loader"
MOVES = "tokens_per_s"


def read(record):
    if not record.get("window_padded_slots"):
        return None
    return 100.0 * record["window_real_tokens"] / record["window_padded_slots"]
