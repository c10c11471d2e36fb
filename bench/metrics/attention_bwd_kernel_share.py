"""Share of the attention core's backward spent in its Pallas kernels, in
%: the device time of the core-line operations under ``attention_bwd``
(``bench/scopes.py``) that are the dK/dV and dQ kernels
(``_flash_bwd_dkv_kernel``, ``_flash_bwd_dq_kernel``), over all of that
scope's.  The rest is the ``D = rowsum(dO * O)`` reduction and the layout
transposes around the kernels.  Nothing is returned when the traced steps
ran no such kernel (a program whose backward is not these kernels)."""
from bench import scopes

KERNELS = ("_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel")


def read(run):
    t = scopes.table(run)
    row = t and t["scopes"].get("attention_bwd")
    if not row:
        return None
    kernels = sum(ms for label, ms in row["ops"] if label in KERNELS)
    return 100.0 * kernels / row["ms"] if kernels else None
