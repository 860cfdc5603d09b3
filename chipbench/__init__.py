"""chipbench — the on-chip benchmark of rabia-tpu's device-plane SMR path.

Everything that decides a number lives here (traffic generation, the
plain reference, the comparison behind ``correct``, the trace reducer,
the table of peaks and the bytes function), so a PR that changes the
program cannot change the yardstick. See README.md.
"""
