"""Seconds of backend compilation during set-up (JAX's
``backend_compile_duration`` events)."""


def read(record):
    return record["setup_compile_s"]
