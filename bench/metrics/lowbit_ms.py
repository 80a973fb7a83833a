"""Device time per epoch of the Low-bit Module's Pallas kernels (the
quantize-and-pack and unpack-and-dequantize ``tpu_custom_call``s of
``kernels/quant``), mean over the cell's chips."""

KERNELS = ("quantize_pack", "unpack_dequantize")


def is_lowbit(ins) -> bool:
    return (ins is not None and ins.target == "tpu_custom_call"
            and any(k in ins.op_name or ins.name.startswith(k)
                    for k in KERNELS))


def read(rec):
    s = rec.per_epoch_device_s(lambda op, ins: is_lowbit(ins))
    return None if s is None else 1e3 * s
