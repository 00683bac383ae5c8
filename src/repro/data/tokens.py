"""Caption vocabulary for the mini-CLIP text tower (perception/clip.py).

Captions are templated from the same class vocabulary as the scene
generator ("a red chair near the wooden table"), a small closed world.
Ids 0 and 1 are reserved (padding, separator).
"""
from __future__ import annotations

from repro.data.scenes import CLASS_NAMES

_ADJ = ["red", "blue", "green", "small", "large", "wooden", "metal", "old",
        "new", "round"]
_REL = ["near", "under", "above", "beside", "behind", "facing"]
_TMPL = ["a {a} {c1} {r} the {c2}", "the {c1} is {r} the {a} {c2}",
         "there is a {a} {c1} {r} the {c2}", "find the {a} {c1}"]

_WORDS = sorted({w for t in _TMPL for w in
                 t.replace("{a}", "").replace("{c1}", "").replace("{c2}", "")
                 .replace("{r}", "").split()} | set(_ADJ) | set(_REL)
                | set(CLASS_NAMES))
VOCAB = {w: i + 2 for i, w in enumerate(_WORDS)}
VOCAB_SIZE = len(VOCAB) + 2

