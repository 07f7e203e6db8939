"""Device memory on the fullest chip: bytes in use after set-up plus the
largest compiler temporaries of the executables the window runs, in GiB.
(The runtime's ``peak_bytes_in_use`` follows live arrays only and misses a
program's scratch.)"""


def read(rec):
    return rec.hbm_bytes / 2 ** 30
