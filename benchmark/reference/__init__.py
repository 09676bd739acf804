"""Plain float32 references, one per model family, keyed by ``model_type``."""

from benchmark.reference import gpt2, neox

FORWARD = {"gpt2": gpt2.forward, "gpt_neox": neox.forward}
