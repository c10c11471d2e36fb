"""Peak device memory, in % of the chip's HBM: the largest, over the
cell's devices, of ``peak_bytes_in_use`` (buffers: parameters, optimizer
state, batches) plus ``peak_bytes_reserved`` (the region the runtime
reserves for the compiled programs' temporaries, which ``peak_bytes_in_use``
leaves out), read after the window, over the HBM of the device kind
(``bench/peaks.json``)."""


def read(run):
    if not run.memory or not run.peak:
        return None
    peak = max(st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
               for st in run.memory)
    return 100.0 * peak / run.peak["hbm_bytes"] if peak else None
