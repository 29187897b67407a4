"""Work of the tiled generation kernel (``kernels/ga/tiling.py``): the
beyond-VMEM population streamed through a grid of (rows x genes) tiles.

Counted from shapes, as the algorithm needs it: each child reads two
parent rows and writes one row, and the selection reads the fitness
vector. The one-hot gather's FLOPs (12 n^2 L at the padded shape) and the
re-reads of the population slice that every output tile makes are
implementation, not work, and are not counted, so a linear row gather
reads the same work. The F15 eval is a kernel of its own (f15_eval).
Bound: memory.
"""
KERNEL = "gen_tiled"
# the HLO instruction in the device trace: a custom call to Mosaic that
# takes the (1, 2) seed first and returns the (rows, genes) population
MATCH = (r"^%\S+ = (?:s8|f32)\[\d+,\d+\]\S* custom-call\(u32\[1,2\]"
         r".*tpu_custom_call")


def work(shape):
    n, L, gb = shape["rows"], shape["length"], shape["gene_bytes"]
    # blend crossover (3 flops) and mutation noise, add and clip (4)
    return {"ops": 7 * n * L * shape["islands_per_call"],
            "bytes": (3 * n * L * gb + n * 4) * shape["islands_per_call"]}
