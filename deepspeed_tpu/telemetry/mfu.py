"""MFU/HFU accounting from compiled-program cost analysis.

Two FLOP ledgers, reported side by side because they answer different
questions:

- **model FLOPs** (``model_flops_per_step``): the 6ND forward+backward
  formula (2ND forward-only for serving decode) — what the model
  mathematically requires.  ``MFU = model_flops / (step_time × devices
  × peak)``; remat recompute and padding never inflate it.
- **hardware FLOPs**: summed ``compiled.cost_analysis()["flops"]`` over
  every registered jitted program × its calls per step — what XLA
  actually scheduled, including remat recompute, so
  ``HFU >= MFU`` and the gap IS the recompute/padding tax.  Because
  the capture preserves shardings, the compiled program (and so its
  cost) is the PER-DEVICE SPMD executable — ``hfu`` therefore divides
  by ``step_time × peak`` alone, while ``mfu`` divides the global model
  FLOPs by ``step_time × n_devices × peak``.

Registration is capture-by-shape: engines register a zero-arg
``make_compiled`` closure (built from ``jax.ShapeDtypeStruct`` trees of
the real dispatch args, under the engine's mesh) the FIRST time a jit
dispatches, and the closure is only invoked lazily at report time —
``lower().compile()`` on shape structs never touches donated buffers
and never runs device code, but it IS a compile, so it stays off the
hot path and outside any recompile-guard window.

Peak FLOPS resolution: an explicit ``peak_tflops_per_device`` config
wins; otherwise the device-kind table below (bf16 peaks); a kind that
is not in it (a CPU mesh, a TPU the table has no row for) reports
achieved FLOPS with ``mfu``/``hfu`` = None rather than a ratio against
a guessed peak.
"""
import threading

import numpy as np

# bf16 peak TFLOPS per chip, keyed by the ``device_kind`` JAX reports,
# lower-cased with the spaces out, and matched exactly (Google Cloud
# documentation of each generation; v5e: "TPU v5e", 197)
PEAK_TFLOPS_TABLE = {
    "tpuv6lite": 918.0, "tpuv6e": 918.0,
    "tpuv5p": 459.0, "tpuv5": 459.0,
    "tpuv5lite": 197.0, "tpuv5e": 197.0,
    "tpuv4": 275.0, "tpuv3": 123.0, "tpuv2": 45.0,
}


def peak_flops_per_device(device_kind):
    """(peak FLOPS/s per device, known) for a device-kind string; a kind
    without a row is unknown, never a neighbour's peak."""
    peak = PEAK_TFLOPS_TABLE.get((device_kind or "").lower().replace(" ", ""))
    if peak is None:
        return None, False
    return peak * 1e12, True


def normalize_cost_analysis(compiled):
    """``compiled.cost_analysis()`` → ``{"flops", "bytes_accessed"}``.

    jax has returned the analysis as a dict, a list of one dict, and (on
    some backends) nothing useful; missing keys come back as None so
    callers can report honestly instead of crashing on a backend quirk.
    """
    try:
        ca = compiled.cost_analysis()
    except (AttributeError, NotImplementedError, RuntimeError) as e:
        return {"flops": None, "bytes_accessed": None, "error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {"flops": None, "bytes_accessed": None}
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed", ca.get("bytes_accessed"))
    return {"flops": float(flops) if flops is not None else None,
            "bytes_accessed": float(nbytes) if nbytes is not None else None}


def model_flops_per_step(n_params, tokens_per_step, fwd_only=False):
    """The dense-transformer FLOP formula: 6ND fwd+bwd, 2ND fwd-only."""
    return (2.0 if fwd_only else 6.0) * float(n_params) \
        * float(tokens_per_step)


def shape_structs(args):
    """``jax.ShapeDtypeStruct`` tree of real dispatch args (non-array
    leaves coerced through numpy), PRESERVING each leaf's NamedSharding:
    a sharded program re-lowered from unsharded structs is a different
    program (and donation aliasing can refuse to compile it at all), so
    the structs must carry the placement for the capture to be faithful.
    Shared by the MFU and memory-accounting registrations."""
    import jax
    from jax.sharding import NamedSharding

    def struct(x):
        if not hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return jax.tree_util.tree_map(struct, args)


def register_by_shape(mfu, name, jit_fn, args, mesh=None,
                      calls_per_step=1.0):
    """THE capture-by-shape registration every engine uses: take a
    ``jax.ShapeDtypeStruct`` tree of the REAL dispatch args NOW (donated
    buffers still alive, shardings preserved) and register a lazy
    ``lower().compile()`` closure — run once, at report time, under
    ``mesh`` when one is given — so the compile never lands on the step
    path or inside a recompile-guard window.  No-op when
    ``mfu``/``jit_fn`` is None or ``name`` is already registered."""
    if mfu is None or jit_fn is None or mfu.has(name):
        return
    import jax

    structs = shape_structs(args)

    def make_compiled():
        if mesh is None:
            return jit_fn.lower(*structs).compile()
        with jax.set_mesh(mesh):
            return jit_fn.lower(*structs).compile()

    mfu.register(name, make_compiled, calls_per_step)


class MfuAccounting:
    """Per-jit FLOPs/bytes registry + MFU/HFU report builder."""

    def __init__(self, peak_tflops_per_device=0.0):
        # explicit peak (TFLOPS) overrides device-kind lookup; 0 = auto
        self.peak_tflops_per_device = float(peak_tflops_per_device or 0.0)
        self._jits = {}        # name -> (make_compiled, calls_per_step)
        self._costs = {}       # name -> normalized cost dict (lazy)
        self._compiled = {}    # name -> compiled object (lazy, shared)
        self._lock = threading.Lock()

    def has(self, name):
        return name in self._jits

    def register(self, name, make_compiled, calls_per_step=1.0):
        """Register one jitted program.  ``make_compiled`` is a zero-arg
        callable returning the compiled object (typically
        ``lambda: jit_fn.lower(*shape_structs).compile()`` under the
        engine's mesh); it runs lazily, once, at report time."""
        with self._lock:
            if name not in self._jits:
                self._jits[name] = (make_compiled, float(calls_per_step))

    def calls_per_step(self, name):
        """Registered calls-per-step factor (None when unregistered)."""
        entry = self._jits.get(name)
        return entry[1] if entry is not None else None

    def compiled(self, name):
        """The lazily-compiled object for one registered program, cached
        so every ledger reading this registry (FLOPs here, bytes in
        runtime/memory_accounting.py) pays ONE ``lower().compile()`` per
        jit between them.  Raises whatever the lowering raised; returns
        None for unregistered names."""
        entry = self._jits.get(name)
        if entry is None:
            return None
        if name not in self._compiled:
            self._compiled[name] = entry[0]()
        return self._compiled[name]

    def costs(self):
        """{name: {flops, bytes_accessed, calls_per_step}} — compiled
        lazily on first call, cached after.  A program whose lowering
        fails reports its error string instead of poisoning the rest."""
        with self._lock:
            jits = dict(self._jits)
        for name, (_make, calls) in jits.items():
            if name in self._costs:
                continue
            try:
                cost = normalize_cost_analysis(self.compiled(name))
            except Exception as e:  # lint: allow-broad-except — one
                # program's lowering quirk must not kill the report
                cost = {"flops": None, "bytes_accessed": None,
                        "error": f"{type(e).__name__}: {e}"}
            cost["calls_per_step"] = calls
            self._costs[name] = cost
        return dict(self._costs)

    def hw_flops_per_step(self):
        total, complete = 0.0, True
        for cost in self.costs().values():
            if cost["flops"] is None:
                complete = False
                continue
            total += cost["flops"] * cost["calls_per_step"]
        return (total if total > 0 else None), complete

    def report(self, *, step_time_s, n_devices, model_flops=None,
               device_kind=None):
        """The ``telemetry_report()["mfu"]`` section.  ``model_flops``
        is per step, all devices; ``step_time_s`` mean seconds per
        optimizer/serving step."""
        hw_flops, complete = self.hw_flops_per_step()
        if self.peak_tflops_per_device > 0:
            peak, peak_known = self.peak_tflops_per_device * 1e12, True
        else:
            peak, peak_known = peak_flops_per_device(device_kind)
        denom = None
        if step_time_s and step_time_s > 0 and n_devices:
            denom = step_time_s * n_devices
        out = {
            "per_jit": self.costs(),
            "hw_flops_per_step": hw_flops,
            "hw_flops_complete": complete,
            "model_flops_per_step": model_flops,
            "step_time_s": step_time_s,
            "n_devices": n_devices,
            "device_kind": device_kind,
            "peak_flops_per_device": peak,
            "peak_known": peak_known,
            "achieved_tflops_per_device":
                (model_flops / denom / 1e12)
                if (denom and model_flops) else None,
            # hw flops are PER-DEVICE (the sharded SPMD executable's own
            # cost_analysis): no n_devices in the hardware denominators
            "achieved_hw_tflops_per_device":
                (hw_flops / step_time_s / 1e12)
                if (step_time_s and hw_flops) else None,
            "mfu": (model_flops / (denom * peak))
            if (denom and model_flops and peak) else None,
            "hfu": (hw_flops / (step_time_s * peak))
            if (step_time_s and hw_flops and peak) else None,
        }
        return out
