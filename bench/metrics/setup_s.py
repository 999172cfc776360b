"""setup_s (s, host clock): process start to the first timed request:
generation, host C-tree, device mirror, compile-cache loads and warm-up."""


def read(rec):
    return rec.setup_s
