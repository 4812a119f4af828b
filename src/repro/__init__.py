"""Reproduction of "Local Thresholding in General Network Graphs"."""
