"""Recurrent-state bytes the window's launches read and wrote through the
arenas (``LaunchRecord.state_bytes``), per document resolved in the
window, in MB (data plane, program counter).  None where the records
carry no such count."""


def read(run):
    docs = len(run.in_window())
    total = sum(getattr(r, "state_bytes", 0) for r in run.launches)
    if not docs or not total:
        return None
    return total / docs / 1e6
