"""Work of the untiled generation megakernel (``kernels/ga/generation.py``):
one island's whole population in VMEM per grid step, vmapped over the
islands of a chip, the problem's fitness fused in.

Counted from shapes, as the algorithm needs it, whatever implements the
gather: each child reads two parent rows and writes one row, and the
kernel reads the fitness vector and writes the fused fitness. One-hot
gather FLOPs, Threefry draws and re-reads that depend on tiling are not
counted, so a row gather or another tiling reads the same work. Bound:
memory (the operations are elementwise and far under any compute peak).
"""
KERNEL = "gen_untiled"
# the Pallas kernel as the device trace names it: its HLO instruction,
# a custom call to Mosaic returning the islands' (k, n, L) population and
# (k, n, 1) fitness
MATCH = (r"^%\S+ = \((?:s8|f32)\[\d+,\d+,\d+\]\S* f32\[\d+,\d+,1\]\S*\) "
         r"custom-call\(.*tpu_custom_call")


def work(shape):
    n, L, gb = shape["rows"], shape["length"], shape["gene_bytes"]
    per_island_bytes = 3 * n * L * gb + 2 * n * 4
    # crossover pick, mutation test and the fused eval's add per gene
    per_island_ops = 3 * n * L
    k = shape["islands_per_call"]
    return {"ops": k * per_island_ops, "bytes": k * per_island_bytes}
