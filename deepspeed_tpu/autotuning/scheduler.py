"""Autotuning resource manager — launcher-driven experiments.

Counterpart of reference ``autotuning/scheduler.py:1`` (``ResourceManager``)
+ ``launcher/runner.py:348`` (``run_autotuning``): trials run as LAUNCHED
subprocesses scheduled onto resource slots, not in-process steps — so
multi-host configurations (host-offload pressure, DCN-visible layouts) are
tunable, and a trial that OOMs or wedges kills its own process, never the
tuner.

Each slot describes where a trial may run:
    {"name": "local"}                      -> plain subprocess on this host
    {"name": "hostA", "launcher_cmd": [...]} -> trial command wrapped by the
        given prefix (e.g. ``["bin/deepspeed-tpu", "--include", "hostA",
        "--num_gpus", "4"]`` — the multinode runners of
        ``launcher/multinode_runner.py`` compose here the same way the
        reference's PDSH/MPI runners carry its autotuner experiments).
    {"env": {...}}                          -> extra environment for trials

Experiments are dicts (see ``autotuning/trial.py``); results land in
per-experiment JSON files under ``exps_dir`` (reference key).
"""

import json
import os
import subprocess
import sys
import time

from ..utils.logging import log_dist, logger


class ResourceManager:
    def __init__(self, slots=None, exps_dir=None, trial_timeout=600):
        self.slots = list(slots) if slots else [{"name": "local"}]
        self.exps_dir = exps_dir or os.path.join(".", "autotuning_exps")
        self.trial_timeout = trial_timeout
        os.makedirs(self.exps_dir, exist_ok=True)

    def _launch(self, exp, slot):
        exp_path = os.path.join(self.exps_dir, f"{exp['exp_id']}.json")
        exp = dict(exp, result_path=os.path.join(self.exps_dir, f"{exp['exp_id']}.result.json"))
        with open(exp_path, "w") as f:
            json.dump(exp, f)
        cmd = list(slot.get("launcher_cmd") or []) + [
            sys.executable, "-m", "deepspeed_tpu.autotuning.trial", "--exp", exp_path]
        env = dict(os.environ)
        # trials get a CLEAN import path: just the repo that owns this
        # package (slot env overrides for real clusters). A trial is its own
        # process and needs the accelerator to itself: the process that runs
        # the tuner must not have initialised JAX on it, or the trial fails
        # at start-up and its stderr lands in the result's "error"
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root
        env.update(slot.get("env") or {})
        proc = subprocess.Popen(cmd, env=env,
                                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
        return {"exp": exp, "slot": slot, "proc": proc, "t0": time.time()}

    def _finish(self, job):
        proc = job["proc"]
        stderr = b""
        try:
            _, stderr = proc.communicate(timeout=max(1, self.trial_timeout
                                                     - (time.time() - job["t0"])))
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            return {"exp_id": job["exp"]["exp_id"], "samples_per_sec": None,
                    "error": f"timeout after {self.trial_timeout}s"}
        rp = job["exp"]["result_path"]
        if os.path.isfile(rp):
            with open(rp) as f:
                res = json.load(f)
        else:
            tail = stderr.decode(errors="replace").strip().splitlines()[-3:]
            res = {"samples_per_sec": None,
                   "error": f"trial process rc={proc.returncode}: {' | '.join(tail)}"}
        res["exp_id"] = job["exp"]["exp_id"]
        return res

    def schedule_experiments(self, exps):
        """Run every experiment, up to ``len(slots)`` concurrently (the
        reference parcels GPUs per experiment the same way). Returns results
        in submission order."""
        pending = list(exps)
        running = []  # (job, slot_idx)
        free = list(range(len(self.slots)))
        results = {}
        while pending or running:
            while pending and free:
                si = free.pop(0)
                job = self._launch(pending.pop(0), self.slots[si])
                running.append((job, si))
                log_dist(f"autotuning: launched {job['exp']['exp_id']} on "
                         f"{self.slots[si].get('name', si)}", [0])
            done_idx = None
            for i, (job, si) in enumerate(running):
                if job["proc"].poll() is not None or \
                        time.time() - job["t0"] > self.trial_timeout:
                    done_idx = i
                    break
            if done_idx is None:
                time.sleep(0.2)
                continue
            job, si = running.pop(done_idx)
            res = self._finish(job)
            if res.get("error"):
                logger.warning(f"autotuning: {res['exp_id']} failed: {res['error']}")
            results[res["exp_id"]] = res
            free.append(si)
        return [results[e["exp_id"]] for e in exps]
