"""The benchmark's one traffic generator.

A traffic mix is a JSON file under `bench/traffic/`, found by the name a
cell gives in `BENCHMARK.json`. It holds parameters only:

    {"base_seed": 11,                  fixes the scenario's structure
     "scenario": {...}}                the scenario's parameters

A scenario is a fat-tree (`racks`, `hosts_per_rack`, `spines`,
`link_gbps`, `prop_delay_s`), a congestion-control setting (`net`: `cc`
plus knobs, the rest from the paper's defaults), and a flow mix
(`size_dist`, `theta`, `sigma`, `max_load`, `matrix`, `num_flows`), the
axes of the paper's Table 2 (arXiv:2503.01770 §5.1).

The structure of the scenario (hosts, paths, the multiset of sizes and of
inter-arrival gaps) comes from `base_seed`, so every run seed presents the
same shapes and the same per-link flow counts to the system. The run seed
only permutes: which flow gets which size, and the order of the gaps.
That keeps the work of every seed the same (no new compile, no new link
degree) while the answers differ.

Copied from the program's Table-2 generator (`repro.data.traffic`,
`repro.net.topology`) so that a later change there does not move the
yardstick; it returns plain numpy arrays and imports nothing of the
program.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

EMPIRICAL = {
    "CacheFollower": ([500, 2e3, 10e3, 50e3, 200e3, 1e6],
                      [0.1, 0.3, 0.55, 0.8, 0.95, 1.0]),
    "WebServer": ([300, 1e3, 3e3, 10e3, 50e3, 200e3],
                  [0.35, 0.6, 0.8, 0.92, 0.99, 1.0]),
    "Hadoop": ([300, 1e3, 5e3, 30e3, 300e3, 2e6],
               [0.5, 0.65, 0.8, 0.9, 0.99, 1.0]),
}
SIZE_BOUNDS = (200, 5e6)
MTU_BYTES = 1000.0
NET_DEFAULTS = dict(init_window=10e3, buffer_bytes=130e3, dctcp_k=20e3,
                    dcqcn_kmin=20e3, dcqcn_kmax=40e3, timely_tlow=50e-6,
                    timely_thigh=125e-6)


@dataclass
class Scenario:
    """One generated scenario, as arrays. Flow i is the i-th arrival."""
    racks: int
    hosts_per_rack: int
    spines: int
    link_gbps: float
    prop_delay_s: float
    net: dict                 # cc + knobs
    src: np.ndarray           # (n,) int64
    dst: np.ndarray
    size: np.ndarray          # (n,) int64 bytes
    t_arrival: np.ndarray     # (n,) float64 seconds, ascending
    paths: list               # n lists of link ids

    @property
    def num_hosts(self) -> int:
        return self.racks * self.hosts_per_rack

    @property
    def num_links(self) -> int:
        return 2 * self.num_hosts + 2 * self.racks * self.spines

    @property
    def num_flows(self) -> int:
        return len(self.size)

    @property
    def capacity(self) -> np.ndarray:
        return np.full(self.num_links, self.link_gbps * 1e9)

    def ideal_fct(self) -> np.ndarray:
        """Unloaded completion time per flow: serialization at the
        bottleneck + propagation + one MTU store-and-forward per later hop
        (all links of a fat-tree here have one capacity)."""
        cap = self.link_gbps * 1e9
        hops = np.array([len(p) for p in self.paths], np.float64)
        ideal = (self.size * 8.0 / cap + hops * self.prop_delay_s
                 + np.maximum(hops - 1, 0) * MTU_BYTES * 8.0 / cap)
        return np.where(hops > 0, ideal, 1e-9)


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", name + ".json")) as fh:
        return json.load(fh)


def scenario(mix: dict, seed: int) -> Scenario:
    """The mix's scenario for run seed `seed`."""
    base = int(mix["base_seed"])
    return generate(mix["scenario"], np.random.default_rng([base, 1000]),
                    np.random.default_rng([seed, 0]))


def sample_sizes(rng, dist: str, n: int, theta: float) -> np.ndarray:
    if dist == "pareto":
        s = (rng.pareto(1.3, n) + 1) * theta * 0.3
    elif dist == "exp":
        s = rng.exponential(theta, n)
    elif dist == "gaussian":
        s = rng.normal(theta, theta / 3, n)
    elif dist == "lognormal":
        s = rng.lognormal(np.log(theta), 0.8, n)
    elif dist in EMPIRICAL:
        pts, cdf = EMPIRICAL[dist]
        logp = np.log(np.array([pts[0] / 3] + list(pts)))
        s = np.exp(np.interp(rng.random(n), np.array([0.0] + list(cdf)),
                             logp))
    else:
        raise ValueError(f"unknown size distribution {dist!r}")
    return np.clip(s, *SIZE_BOUNDS).astype(np.int64)


def traffic_matrix(rng, kind: str, racks: int) -> np.ndarray:
    """Rack-to-rack probabilities: A uniform-ish, B hot racks, C local."""
    if kind == "A":
        m = np.ones((racks, racks)) + 0.3 * rng.random((racks, racks))
    elif kind == "B":
        hot = rng.random(racks) ** 3
        m = np.outer(hot + 0.1, np.ones(racks)) + 0.2
    elif kind == "C":
        m = 0.3 * np.ones((racks, racks)) + 3.0 * np.eye(racks)
    else:
        raise ValueError(f"unknown traffic matrix {kind!r}")
    np.fill_diagonal(m, m.diagonal() * 0.5)
    return m / m.sum()


def ecmp_path(racks, hpr, spines, src, dst, flow_id):
    """Link ids host->tor->spine->tor->host, spine by flow hash."""
    H = racks * hpr
    rs, rd = src // hpr, dst // hpr
    if src == dst:
        return []
    if rs == rd:
        return [src, H + dst]
    s = (flow_id * 2654435761 + src * 97 + dst) % spines
    return [src, 2 * H + rs * spines + s,
            2 * H + racks * spines + rd * spines + s, H + dst]


def generate(p: dict, base_rng, run_rng) -> Scenario:
    """One scenario: structure from `base_rng`, permutation from
    `run_rng` (see the module docstring)."""
    racks, hpr, spines = p["racks"], p["hosts_per_rack"], p["spines"]
    n = int(p["num_flows"])
    H = racks * hpr
    sizes = sample_sizes(base_rng, p["size_dist"], n, p.get("theta", 20e3))
    tm = traffic_matrix(base_rng, p["matrix"], racks)
    pairs = base_rng.choice(racks * racks, size=n, p=tm.reshape(-1))
    src = (pairs // racks) * hpr + base_rng.integers(0, hpr, n)
    dst = (pairs % racks) * hpr + base_rng.integers(0, hpr, n)
    same = src == dst
    dst[same] = (dst[same] + 1) % H
    paths = [ecmp_path(racks, hpr, spines, int(s), int(d), i)
             for i, (s, d) in enumerate(zip(src, dst))]
    # load targeting: bits per flow on the busiest link at unit rate
    per_link = np.zeros(2 * H + 2 * racks * spines)
    for path, sz in zip(paths, sizes):
        per_link[path] += sz * 8.0
    mean_gap = per_link.max() / n / (p["max_load"] * p["link_gbps"] * 1e9)
    sigma = p["sigma"]
    gaps = base_rng.lognormal(np.log(max(mean_gap, 1e-9)) - sigma ** 2 / 2,
                              sigma, n)
    # the run seed permutes sizes over flows and the order of the gaps
    sizes = sizes[run_rng.permutation(n)]
    gaps = gaps[run_rng.permutation(n)]
    t_arr = np.cumsum(gaps)
    t_arr -= t_arr[0]
    net = dict(NET_DEFAULTS)
    net.update(p.get("net", {}))
    net.setdefault("cc", "dctcp")
    return Scenario(racks=racks, hosts_per_rack=hpr, spines=spines,
                    link_gbps=float(p["link_gbps"]),
                    prop_delay_s=float(p.get("prop_delay_s", 1e-6)),
                    net=net, src=src.astype(np.int64),
                    dst=dst.astype(np.int64), size=sizes,
                    t_arrival=t_arr, paths=paths)


def cfg_vec(net: dict) -> np.ndarray:
    """m4's 9-wide network-config input (paper §3.4): CC one-hot and the
    knobs, each over its Table-2 upper end."""
    one_hot = {"dctcp": [1, 0, 0], "dcqcn": [0, 1, 0],
               "timely": [0, 0, 1]}[net["cc"]]
    return np.array(one_hot + [
        net["init_window"] / 15e3, net["buffer_bytes"] / 160e3,
        net["dctcp_k"] / 30e3, net["dcqcn_kmin"] / 30e3,
        net["dcqcn_kmax"] / 50e3, net["timely_thigh"] / 150e-6],
        np.float32)
