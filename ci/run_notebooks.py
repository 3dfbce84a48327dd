"""Execute every notebook under notebooks/ headless (nbclient), as the CI
notebook gate. TPUML_NB_CPU=1 is exported so the notebooks pin themselves
to the CPU backend with 8 virtual devices (CI machines have no chip).

Usage: python ci/run_notebooks.py [name.ipynb ...]
"""
import os
import sys
import time

import nbclient
import nbformat

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NB_DIR = os.path.join(HERE, "notebooks")


def main():
    os.environ["TPUML_NB_CPU"] = "1"
    # kernels launch with cwd=notebooks/; the repo root must be importable
    # (demo.ipynb imports the package before it can fix sys.path itself)
    os.environ["PYTHONPATH"] = HERE + os.pathsep + os.environ.get(
        "PYTHONPATH", ""
    )
    names = sys.argv[1:] or sorted(
        f for f in os.listdir(NB_DIR) if f.endswith(".ipynb")
    )
    failed = []
    for name in names:
        path = os.path.join(NB_DIR, name)
        nb = nbformat.read(path, as_version=4)
        t0 = time.time()
        try:
            nbclient.NotebookClient(
                nb, timeout=600, kernel_name="python3",
                resources={"metadata": {"path": NB_DIR}},
            ).execute()
            print(f"[nb] {name}: OK ({time.time() - t0:.1f}s)")
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"[nb] {name}: FAILED — {str(e)[:400]}")
    if failed:
        sys.exit(f"notebooks failed: {failed}")
    print(f"[nb] all {len(names)} notebooks executed")


if __name__ == "__main__":
    main()
