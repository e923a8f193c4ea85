"""One kernel's device time a run of the main program: the trace's
``kernels`` row named ``kernel`` over the main module's ``module_runs``,
times ``scale``.  Unlike ``trace_value``'s ``kernel_s``, no other custom
call of the program (an expert layer's grouped products, say) is counted.
A trace without that row, or without runs of the main program, reads
nothing."""


def read(run: dict, kernel: str, scale: float = 1.0):
    trace = run["trace"]
    if not trace:
        return None
    seconds = dict(trace.get("kernels", [])).get(kernel)
    runs = trace.get("module_runs", {}).get(run["traffic"]["main_module"])
    if not seconds or not runs:
        return None
    return scale * seconds / runs
