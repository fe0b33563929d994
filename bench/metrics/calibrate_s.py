"""Host clock around ``CNN2Gate.calibrate_quantization``: the float pass
on the host's CPU device, scales, quantization and the static checks."""


def read(rec):
    return rec["spans"].get("calibrate")
