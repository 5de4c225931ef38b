"""Distributed execution: device meshes, ICI collective exchange, multi-host.

The reference's distributed story is the UCX shuffle (SURVEY.md §2.4/§5.8:
RDMA active messages + bounce buffers + peer discovery).  The TPU-native
answer has three tiers: when a whole stage is resident on a mesh, a shuffle
*is* an XLA collective (all_to_all over ICI) inside one shard_mapped program
— no RPC, no serialization (exchange.py/distributed.py); within one process
the host-staged shuffle (host_shuffle.py) plays the reference's
multithreaded-mode role; BETWEEN hosts the DCN process group (dcn.py) adds
rendezvous, heartbeats, and TCP peer-to-peer partition fetch — the UCX
transport analog, with the host-shuffle frame file as the wire format.
"""

from .dcn import (Coordinator, DcnShuffle, PeerFailedError,  # noqa: F401
                  ProcessGroup, run_distributed_agg,
                  run_distributed_query)
