"""Mean duration, in ms, of the first chip's program executions (the
trace's ``XLA Modules`` line) whose name matches ``module``.  The first
and the last execution of the trace are left out: the traced window can
cut either (a decode step that was running as the trace began read 15%
short on a v5e, PR 25).  A program that compiles every jitted body as
``jit_fn`` matches nothing: None."""

import re


def read(spec, numbers, reduced, peaks):
    rx = re.compile(spec["module"])
    modules = sorted(reduced["first"]["modules"], key=lambda m: m[1])
    hits = [d for name, _, d in modules[1:-1] if rx.search(name)]
    if not hits:
        return None
    print(f"INFO trace_module {spec['module']}: {len(hits)} executions of "
          f"{min(hits) / 1e6:.3f} to {max(hits) / 1e6:.3f} ms", flush=True)
    return sum(hits) / len(hits) / 1e6
