"""Device: bytes the node's connectors hold in tables on the device, in
GB (1e9 bytes), from the program's own account of them
(``exec.memory.resident_table_bytes``: reserved when a table is written,
released when it is dropped; the window only reads, so the account at
the window's end is the account at its opening).  Beside
``peak_hbm_gb``.  None where the program keeps no such account."""


def read(run):
    try:
        from trino_tpu.exec.memory import resident_table_bytes
    except ImportError:
        return None
    return resident_table_bytes() / 1e9
