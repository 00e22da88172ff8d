"""The work that ``Y = S A`` needs, from the problem alone, whatever
implements it: A read once from HBM at its stored type, Y written once in
fp32, and one multiply-add per nonzero of S per column of A.

nnz(S) is fixed by the sketch's definition: κ·s nonzeros in every column
of a BlockPerm-SJLT (s per block, in each of κ blocks), s in every column
of a global family (CountSketch, the sparse-graph sketch).
"""
from __future__ import annotations

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float8": 1}


def nnz(sk: dict) -> int:
    per_col = sk["kappa"] * sk["s"] if sk["family"] == "blockperm" \
        else sk["s"]
    return per_col * sk["d"]


def work(sk: dict, n: int, operand_dtype: str = "float32"):
    """(bytes, operations) of one application to a (d, n) operand."""
    nbytes = sk["d"] * n * ITEMSIZE[operand_dtype] + sk["k"] * n * 4
    return nbytes, 2 * nnz(sk) * n


def ideal_s(sk: dict, n: int, peaks: dict,
            operand_dtype: str = "float32") -> float:
    """The least time the chip could take: the larger of the bytes over
    the HBM bandwidth and the operations over the bf16 peak."""
    nbytes, ops = work(sk, n, operand_dtype)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               ops / peaks["bf16_flops_per_s"])
