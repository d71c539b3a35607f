"""`ds_report` — environment and op compatibility report.

Reference behavior: deepspeed/env_report.py:23-109 (op install/compat
table + framework versions). TPU version reports the jax stack, devices,
and which native/Pallas ops are active.
"""
GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
NO = f"{RED}[NO]{END}"


def op_report():
    lines = []
    lines.append("-" * 74)
    lines.append("op name " + "." * 40 + " compatible")
    lines.append("-" * 74)
    from deepspeed_tpu.ops.op_builder import ALL_OPS

    for name, builder_cls in ALL_OPS.items():
        builder = builder_cls()
        status = OKAY if builder.is_compatible() else NO
        lines.append(f"{name} {'.' * (48 - len(name))} {status}")
    # kernel paths: compiled on a TPU backend, interpreted on the CPU
    # backend; a backend that failed to start is reported as that
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        pallas = f"{NO} backend failed to start: {e}"
    else:
        pallas = OKAY if backend == "tpu" else \
            f"{YELLOW}[interpret-mode (backend is {backend})]{END}"
    lines.append(f"pallas_flash_attention {'.' * 26} {pallas}")
    lines.append("-" * 74)
    return "\n".join(lines)


def version_report():
    import jax

    import deepspeed_tpu

    lines = []
    lines.append("DeepSpeed-TPU general environment info:")
    try:
        import jaxlib

        lines.append(f"jax version ................... {jax.__version__}")
        lines.append(f"jaxlib version ................ {jaxlib.__version__}")
    except ImportError:  # pragma: no cover
        pass
    try:
        import flax

        lines.append(f"flax version .................. {flax.__version__}")
    except ImportError:
        pass
    lines.append(f"deepspeed_tpu version ......... {deepspeed_tpu.__version__}")
    lines.append(
        f"reference API version ......... "
        f"{deepspeed_tpu.__reference_version__}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        lines.append(f"devices ....................... unavailable ({e})")
    else:
        plats = {}
        for d in devices:
            plats[d.platform] = plats.get(d.platform, 0) + 1
        desc = ", ".join(f"{n}x {p}" for p, n in plats.items())
        lines.append(f"devices ....................... {desc}")
    return "\n".join(lines)


def main(args=None):
    print(op_report())
    print(version_report())
    return 0


cli_main = main

if __name__ == "__main__":
    main()
