"""Share of the traced steps' core-line device time that the program
names, in %: the operations under any program scope (``bench/scopes.py``)
over all of them.  The rest, ``unscoped``, is what XLA makes itself (async
copies, combined collectives) and what no scope covers.  Also writes the
whole attribution (``scopes.table``: each scope's ms a step and share, its
longest operations, the longest unscoped ones with their ``op_name``s) as
one stderr line headed ``scopes``.  Nothing is returned, and nothing
written, when the program names no scope."""
import json
import sys

from bench import scopes

TOP = 16


def read(run):
    found = scopes.by_scope(run)
    total = sum(found.values())
    named = total - found.get(scopes.UNSCOPED, 0.0)
    if not named:
        return None
    print("scopes", json.dumps(scopes.table(run, top=TOP)), file=sys.stderr, flush=True)
    return 100.0 * named / total
