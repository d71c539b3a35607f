"""A stand-in architecture for the tests: GPT-2's file, but the PROGRAM's
model is built with another LayerNorm epsilon than the configuration
states and the reference reads.  The program then serves another function
than the reference computes, which a run has to report as not correct."""
import os

from harness import cells

_gpt2 = cells.load_module(os.path.join(
    cells.BENCH_DIR, "architectures", "gpt2.py"), "bench_arch_gpt2_wrong_eps")
globals().update({name: value for name, value in vars(_gpt2).items()
                  if not name.startswith("__")})


def build_model(config, overrides):
    return _gpt2.build_model(dict(config, layer_norm_epsilon=0.3), overrides)
