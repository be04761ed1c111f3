"""Job data strings.  Window keys are lowercase letters and digits drawn from
the seed; warm-up keys are uppercase letters and fixed, so that a warm-up
never answers a window job from a cache and set-up is the same work in
every run."""

from __future__ import annotations

import random
import string

_WINDOW = string.ascii_lowercase + string.digits
_WARM = string.ascii_uppercase


def window_key(seed: int, name: str, length: int) -> str:
    rng = random.Random(f"{seed}/{name}")
    return "".join(rng.choice(_WINDOW) for _ in range(length))


def warm_key(i: int, length: int) -> str:
    rng = random.Random(f"warm/{i}")
    return "".join(rng.choice(_WARM) for _ in range(length))
