"""What the drivers share."""
import numpy as np

# A program's random_seed is a compile-time constant in this repo
# (LowerContext.rng folds it into the traced code), so a new seed would be a
# new compilation. The drivers keep it fixed — dropout masks repeat across
# seeds, the compiled programs are the same for every seed — and take
# tokens, prompts and weights from --seed.
PROGRAM_SEED = 24


def scalar(x):
    return float(np.asarray(x).reshape(-1)[0])


def compile_misses(delta):
    """compile_cache_miss* movement in a monitor.counter_delta()."""
    return sum(v for k, v in delta.items()
               if k.startswith('compile_cache_miss'))
