"""Device bytes of the column store's resident columns after the window
(``ColumnStore.total_device_bytes``), in GB."""


def read(run):
    return run.resident_bytes / 1e9
