"""Operations and bytes of block-move gain scoring, counted from shapes.

One solver step scores every (block size m, type p, source s, destination
d) move of every instance of the batch, whatever implements it (the Pallas
kernel or its XLA twin): M * k * l * l * B gain elements, M the ladder
length. Per step and instance the closed forms cost:

  * column rates X_j: k*l multiplies, 2*k*l adds, l divides  -> 3*k*l + l
  * per (m, p, j): the add and remove closed forms (4 + 5 operations),
    the feasibility compare and select (2)                    -> 11 * M*k*l
  * per gain element: the pair sum and the diagonal select   -> 2 * M*k*l*l
  * selection: two max reductions over the m=1 directions, the
    first-index search and the ladder walk (4 per size)       -> 3*k*l*l + 4*M

Bytes per step are what must cross HBM at the least: the states and rates
read (2 * B*k*l float32) and the chosen move written (3 * B words); the
gains themselves are never written.
"""
from __future__ import annotations


def gain_scoring(B: int, k: int, l: int, n_sizes: int,
                 steps: int) -> tuple[float, float]:
    """(operations, bytes) of `steps` scoring passes over a (B, k, l) batch
    with an `n_sizes` doubling ladder."""
    M = n_sizes
    per = (3 * k * l + l + 11 * M * k * l + 2 * M * k * l * l
           + 3 * k * l * l + 4 * M)
    ops = float(B) * per * steps
    nbytes = float(B) * (2 * k * l * 4 + 3 * 4) * steps
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of the compute and the memory bound, and
    which of the two it is."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_mem else (t_mem, "hbm")
