"""Sparse-expert sequence model (next-token + MTP loss) on the T2R model
contract: one chip's share of a wider expert group, training."""
