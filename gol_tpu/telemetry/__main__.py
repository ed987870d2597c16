"""``python -m gol_tpu.telemetry
{summarize <dir> | diff <a> <b> | watch <dir> | postmortem <dir> |
 trace <dir> [--request ID] [--perfetto out.json] [--slo FILE]}``."""

import sys

from gol_tpu.telemetry.summarize import main

if __name__ == "__main__":
    sys.exit(main())
