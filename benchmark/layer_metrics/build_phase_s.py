"""Seconds of set-up in one phase of building the program's executables,
summed over every `kind`, read from the process's metrics registry (the
reader runs in the process that ran the set-up). params: {"phase"}:

- "trace", "lower", "compile": the self seconds of jax's phases inside
  `counted_jit`'s builds, `dl4j_compile_phase_seconds_total{kind,phase}`
  (a nested phase counted once, for the innermost span; "compile" is a
  read of jax's persistent cache where it hits);
- "outside": `setup_s` less the sum of `dl4j_compile_seconds` (every
  build with its first execution): imports, the TPU client, the inputs,
  the driver's own jits and the warm steps.

None where the program built nothing through `counted_jit`, and for a
phase where it keeps no phase counter (a program older than PR 37)."""


def read(ctx, params):
    from deeplearning4j_tpu.common.metrics import registry
    builds = registry().get("dl4j_compile_seconds")
    if builds is None or not sum(c.count() for _, c in builds.children()):
        return None
    phase = params["phase"]
    if phase == "outside":
        return ctx["end_to_end"]["setup_s"] - sum(
            c.sum() for _, c in builds.children())
    phases = registry().get("dl4j_compile_phase_seconds_total")
    if phases is None:
        return None
    return sum(c.value() for (_, p), c in phases.children() if p == phase)
