"""Work the device SA step has to do, counted from the algorithm.

Per chain and step, a proposed swap of two slots reads both nodes' rows of
the incident-edge table (partner id int32, volume float32 and direction
flag int8: 2 x D entries each), the partner nodes' current cores (2 x D
int32), the hop counts before and after the swap for each incident edge
(8 x D float32: source and destination cores, before and after), the two
swapped slots (2 int32), and writes the two slots and the running cost (2
int32 + 1 float32). ``D`` is the largest incident degree of the graph. The
count depends only on the graph and the number of chains, not on how a
step is implemented.
"""

INT32 = FLOAT32 = 4
INT8 = 1


def sa_step_bytes(degree: int, chains: int) -> int:
    d2 = 2 * degree
    per_chain = (d2 * (INT32 + FLOAT32 + INT8)   # incident-table rows
                 + d2 * INT32                    # partners' current cores
                 + 4 * d2 * FLOAT32              # hops before and after
                 + 2 * INT32                     # the two slots read
                 + 2 * INT32 + FLOAT32)          # two slots and cost written
    return per_chain * chains
