"""Demo: run RAFT on a folder of frames and write flow visualizations.

Reference ``demo.py:42-63``: glob frames, pad, ``iters=20, test_mode``,
colorize with the Middlebury wheel. The reference pops an OpenCV window;
headless TPU hosts are the norm here, so images are written to ``--out``
(pass ``--show`` to also try a window).
"""

from __future__ import annotations

import argparse
import os
import os.path as osp
from glob import glob

import numpy as np
from PIL import Image

from raft_tpu.evaluate import (ASSETS_DIR, load_predictor,
                               reject_raft_only_flags)
from raft_tpu.families import FLOW_FAMILIES
from raft_tpu.utils.flow_viz import flow_to_image
from raft_tpu.utils.padder import InputPadder


def demo(args) -> None:
    predictor = load_predictor(args.model, small=args.small,
                               alternate_corr=args.alternate_corr,
                               mixed_precision=args.mixed_precision,
                               iters=args.iters,
                               model_family=args.model_family,
                               corr_dtype=args.corr_dtype)
    os.makedirs(args.out, exist_ok=True)

    images = sorted(glob(osp.join(args.path, "*.png"))
                    + glob(osp.join(args.path, "*.jpg")))
    for imfile1, imfile2 in zip(images[:-1], images[1:]):
        image1 = np.asarray(Image.open(imfile1), np.float32)[..., :3]
        image2 = np.asarray(Image.open(imfile2), np.float32)[..., :3]
        padder = InputPadder(image1.shape)
        im1, im2 = padder.pad(image1, image2)
        _, flow = predictor(im1, im2)
        flow = padder.unpad(flow)

        viz = flow_to_image(flow)
        side_by_side = np.concatenate(
            [image1.astype(np.uint8), viz], axis=0)
        out_file = osp.join(args.out,
                            osp.splitext(osp.basename(imfile1))[0]
                            + "_flow.png")
        Image.fromarray(side_by_side).save(out_file)
        print(out_file)

        if args.show:
            try:
                import cv2
                cv2.imshow("flow", side_by_side[:, :, ::-1] / 255.0)
                cv2.waitKey(1)
            except Exception:
                pass


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", required=True,
                        help="torch .pth, orbax checkpoint dir, or 'random' "
                             "(pipeline smoke test, random weights)")
    parser.add_argument("--path", default=None,
                        help="directory of ordered frames (default: the "
                             "repo-owned assets/demo-frames fixtures)")
    parser.add_argument("--out", default="demo_out")
    parser.add_argument("--model_family", default="raft",
                        choices=list(FLOW_FAMILIES))
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--iters", type=int, default=None,
                        help="refinement iterations (canonical RAFT "
                             "only; default 20, reference demo.py:62)")
    parser.add_argument("--alternate_corr", action="store_true")
    parser.add_argument("--corr_dtype", default=None,
                        choices=["float32", "bfloat16", "auto"])
    parser.add_argument("--mixed_precision", action="store_true")
    parser.add_argument("--show", action="store_true")
    args = parser.parse_args(argv)
    from raft_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    reject_raft_only_flags(parser, args)
    if args.iters is None:
        args.iters = 20          # reference demo.py:62
    if args.path is None:
        args.path = osp.join(ASSETS_DIR, "demo-frames")
    demo(args)


if __name__ == "__main__":
    main()
