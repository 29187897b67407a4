"""Work of the streaming CEC2010-F15 eval kernel
(``kernels/rastrigin/rastrigin.py``): rotate each m-wide group of the
shifted, permuted population by its own m x m matrix and reduce the
Rastrigin terms.

Counted from the problem's shapes: read the n x D population once and
the D/m rotations, write n fitness values; 2 m FLOPs per coordinate for
the rotation and 5 for the Rastrigin term. The zero padding of m to the
MXU width and the passes a precision mode takes are implementation, not
work. Bound: memory at D=1000, m=50 (about 1e9 FLOPs against 40 MB).
"""
KERNEL = "f15_eval"
# the HLO instruction in the device trace: a custom call to Mosaic from
# the (rows, groups x lanes) population and the rotation stack to a
# (rows, 1) column
MATCH = (r"^%\S+ = f32\[\d+,1\]\S* custom-call\(f32\[\d+,\d+\]\S* %\S+, "
         r"f32\[\d+,\d+,\d+\].*tpu_custom_call")


def work(shape):
    n, D, m = shape["rows"], shape["length"], shape["group"]
    k = shape["islands_per_call"]
    return {"ops": k * n * D * (2 * m + 5),
            "bytes": k * (n * D * 4 + n * 4) + D * m * 4}
