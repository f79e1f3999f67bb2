"""Parameter stream `tpch_qgen_q9`: QGEN's substitution parameter of
TPC-H Q9, drawn afresh for every execution (specification rev. 3, clause
2.4.9.3; clause number and list from memory).

  (pattern,): '%' + COLOR + '%', COLOR uniform over the 92 words P_NAME is
  made of, as the text a driver would bind ("%green%"): 92 bindings.

The words are the specification's list as benchmark/loaders/tpch_pname.py
has it (WORDS); it cannot be imported here, it pulls in JAX, so the list
is written out and tests/test_q9.py holds the two equal. No spec field is
read: a rehearsal draws from the same 92 (SF 0.01 has about 108 parts a
colour).

The harness seeds `rng` from (--seed, client). Runs in the client child:
numpy and the standard library only.

draw(spec, rng, size, state) -> list of 1-tuples of str;
corners(spec) -> the first and the last word of the list, for the warm-up
step (benchmark/warmup/qgen_domain.py): the 92 selectivities differ by
about 1% of themselves, so no binding can overflow a capacity that
another does not.
"""

from __future__ import annotations

import numpy as np

COLORS = (
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace",
    "lavender", "lawn", "lemon", "light", "lime", "linen", "magenta",
    "maroon", "medium", "metallic", "midnight", "mint", "misty", "moccasin",
    "navajo", "navy", "olive", "orange", "orchid", "pale", "papaya", "peach",
    "peru", "pink", "plum", "powder", "puff", "purple", "red", "rose",
    "rosy", "royal", "saddle", "salmon", "sandy", "seashell", "sienna",
    "sky", "slate", "smoke", "snow", "spring", "steel", "tan", "thistle",
    "tomato", "turquoise", "violet", "wheat", "white", "yellow",
)


def _pattern(word: str):
    return ("%" + word + "%",)


def prepare(spec: dict):
    return COLORS


def draw(spec: dict, rng: np.random.Generator, size: int, state):
    return [_pattern(state[i]) for i in rng.integers(0, len(state), size)]


def corners(spec: dict):
    return [_pattern(COLORS[0]), _pattern(COLORS[-1])]
