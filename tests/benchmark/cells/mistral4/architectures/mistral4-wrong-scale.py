"""A stand-in architecture for the tests: Mistral 4's file, but the
PROGRAM's model is built without the position-dependent query scale
(``llama_4_scaling_beta`` 0) and with YaRN's ``mscale_all_dim`` 0, which
the configuration states and the reference reads.  The program then serves
another function than the reference computes (another softmax scale, and
another query scale beyond the original context), which a run has to
report as not correct."""
import os

from harness import cells

_m4 = cells.load_module(os.path.join(
    cells.BENCH_DIR, "architectures", "mistral4.py"),
    "bench_arch_mistral4_wrong_scale")
globals().update({name: value for name, value in vars(_m4).items()
                  if not name.startswith("__")})


def build_model(config, overrides):
    rope = dict(config["rope_parameters"], llama_4_scaling_beta=0.0,
                mscale_all_dim=0.0)
    return _m4.build_model(dict(config, rope_parameters=rope), overrides)
