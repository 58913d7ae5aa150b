"""Device-busy time outside the MLA Pallas calls, as a share of device-busy
time in the traced window (%): the latent's decompression, the query's
absorption and un-absorption, operand builds and checksums.  None where no
MLA kernel call was traced."""
KERNELS = ("mla_prefill", "mla_decode")


def read(rec):
    d = rec.device
    if d is None:
        return None
    calls = [s for p, (n, s) in d["calls"].items() if n and rec.kernel_of.get(p) in KERNELS]
    busy = sum(d["busy_s"])
    if not calls or busy <= 0:
        return None
    return 100.0 * (busy - sum(calls)) / busy
