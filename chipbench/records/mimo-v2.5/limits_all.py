"""``tools/check_limits_knobs.py`` over ALL eight degraded references that
ISSUE 49 lists: the six the family's limits must refuse
(``families/mimo_v2.py::DEGRADED``) and the two no rule on served tokens can
(``NOT_TOLD_APART_ON_THE_CHIP``), so that every one's readings are on
record. Exit 1 is then expected: the tool holds every reading but the
program's to fail.

    python3 chipbench/records/mimo-v2.5/limits_all.py --workload \
        mimo-v2.5.serve-code-agent --seed <n> --seconds 20
"""

import sys

from chipbench.families import mimo_v2 as family
from chipbench.tools import check_limits_knobs

family.DEGRADED = {**family.DEGRADED, **family.NOT_TOLD_APART_ON_THE_CHIP}
sys.exit(check_limits_knobs.main())
