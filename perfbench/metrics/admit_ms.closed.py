from perfbench.readers import admit_ms as read  # noqa: F401
