"""device.compiles_in_window: programs lowered in the window, each of them
compiled or read from the compile cache (JAX's lowering events); set-up
warms every program the traffic runs, so this should read 0."""


def read(run):
    return float(sum(e[0].endswith("jaxpr_to_mlir_module_duration")
                     for e in run.compiles))
