"""What the harness needs of a store (``bench/stores/<kind>.py``
``build(cfg, seed)`` returns one)."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Store:
    frozen: object              # the program's FrozenTrie
    data: dict                  # raw data: what the reference reads
    popular_items: np.ndarray   # the catalogue, most popular first
    info: dict                  # sizes and set-up times, for the log
