"""A stand-in architecture for the tests: LongCat-Flash's file, but the
PROGRAM's model is built without the scale correction of the two low-rank
paths (``mla_scale_q_lora`` and ``mla_scale_kv_lora`` false), which the
configuration states and the reference reads.  The program then serves
another function than the reference computes (queries half as large, keys
and values 12**-0.5 as large at the published ranks), which a run has to
report as not correct."""
import os

from harness import cells

_longcat = cells.load_module(os.path.join(
    cells.BENCH_DIR, "architectures", "longcat_flash.py"),
    "bench_arch_longcat_no_scale")
globals().update({name: value for name, value in vars(_longcat).items()
                  if not name.startswith("__")})


def build_model(config, overrides):
    return _longcat.build_model(dict(config, mla_scale_q_lora=False,
                                     mla_scale_kv_lora=False), overrides)
