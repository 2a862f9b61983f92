import sys

from perfbench.runner import main

sys.exit(main())
