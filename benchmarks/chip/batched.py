"""The program's population compile buckets (``core/batched_eval.py``),
copied so the yardstick does not move with the program: a dispatch of n
candidates runs ``bucket_size(n)`` lanes."""

BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def bucket_size(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // BUCKETS[-1]) * BUCKETS[-1]
