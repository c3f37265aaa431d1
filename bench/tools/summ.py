"""Print one line per JSON result line of a calibration or benchmark run."""
import json
import sys

for path in sys.argv[1:]:
    print("==", path)
    for line in open(path):
        if not line.startswith("{"):
            continue
        d = json.loads(line)
        if "crashed" in d:
            print(" ", d)
            continue
        m = {k: round(v["value"], 4) for k, v in d.get("metrics", {}).items()}
        chk = {k: v["value"] for k, v in d.get("check", {}).items()}
        head = {k: d[k] for k in ("seed", "run", "layers", "rate_per_s", "attempted", "failed",
                                  "correct") if k in d}
        print(" ", head, m, "check", chk, d.get("detail") or d.get("check_detail") or "",
              "mem", d.get("device", {}).get("memory_peak_bytes", ""))
