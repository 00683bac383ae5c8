"""End-to-end training driver: the ~110M-parameter SemanticXR captioner LM
trained for a few hundred steps on the scene-caption corpus, with
checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_captioner.py [--steps 200]

(Thin wrapper over repro.launch.train — the same launcher that drives the
production mesh; see also --kill-at for the fault-injection demo.)
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    enable_compile_cache()
    args = sys.argv[1:] or ["--steps", "200", "--batch", "8", "--seq", "256"]
    main(["--arch", "semanticxr-captioner-110m"] + args)
