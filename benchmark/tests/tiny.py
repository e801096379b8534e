"""Tiny presets of the cells for the CPU rehearsals: the cell's own
files with the image, batch, ladder and CEM width cut down, and the
model computing in float32, so that a sound run keeps the cell's own
limits at a size where bfloat16 would not (few rows and positions to
average its rounding over)."""

import time

import jax.numpy as jnp

from benchmark import harness

PEAKS = {"bf16_flops_per_s": 1e12}


def train_cell(workload, image=48, batch=8, steps=2):
  cell = harness.load_cell(workload)
  cell.config["image_size"] = image
  cell.config["model"]["kwargs"] = {"image_size": image,
                                    "compute_dtype": jnp.float32}
  cell.traffic.update(batch_per_chip=batch, scan_steps=steps)
  return cell


def serve_cell(workload="qtopt_serve_closed64", image=48):
  cell = harness.load_cell(workload)
  cell.config["image_size"] = image
  cell.config["model"]["kwargs"] = {"image_size": image,
                                    "compute_dtype": jnp.float32}
  cell.config["cem_num_samples"] = 32
  cell.traffic.update(robots_per_chip=4, frames_per_chip=12,
                      ladder_sizes=[1, 4], compare_requests=48)
  return cell


def run(cell, devices, seed=2147483999, seconds=0.5, trace=False):
  return harness.run_cell(cell, seed, seconds, trace, devices, time.time(),
                          peaks=PEAKS)
